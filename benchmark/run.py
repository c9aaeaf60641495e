"""Entry point: one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

Prints the result as the last line of stdout, and the numbers compared
with the reference, each beside its limit, as the last lines of stderr.
Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is not there.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the compile cache lives at one fixed path inside the checkout, whatever
# the environment says: only the first run of a cell there compiles
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: compare the control's answers in place of "
                         "the program's (must come out not correct)")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), control=bool(args.control),
                             t_start=T_START)
    except harness.NoDevice as e:
        print(e, file=sys.stderr)
        return 3
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
