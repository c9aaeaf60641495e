"""Serving-headline bench: the full closed-loop oracle-checked run.

Drives the real HTTP surface with hundreds of concurrent sessions
(editor-replay + burst + shed-and-read + one giant chunked-merge racer,
``crdt_graph_tpu/bench/loadgen.py``) while the online session-guarantee
oracle (``crdt_graph_tpu/obs/oracle.py``) checks read-your-writes,
monotonic reads, dropped acks, and convergence from the trace/flight
stream.  Writes the committed serving-headline artifact
(``BENCH_SERVE_r01_cpu.json``): sustained merged ops/sec, reader
p50/p99 under load, violation count (must be 0), next to the kernel
headline (docs/SERVING.md).

Run: ``python scripts/bench_serve_headline.py [sessions] [writes]
[out_path]`` — defaults 200 sessions x 24 writes x 12 leaves (+ a
140k-op giant racer) ≈ 200k total leaves, minutes on the CPU driver
box.  Exits non-zero on any oracle violation or session error.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


def run(n_sessions: int = 200, writes_per_session: int = 24,
        out_path: str = None, delta_size: int = 12, n_docs: int = 8,
        giant_ops: int = 140_000, seed: int = 1) -> dict:
    from crdt_graph_tpu.bench import loadgen

    cfg = loadgen.LoadgenConfig(
        n_sessions=n_sessions, n_docs=n_docs,
        writes_per_session=writes_per_session, delta_size=delta_size,
        max_queue_requests=16,   # < sessions-per-doc: the staged first
                                 # round guarantees 429 shedding
        giant_ops=giant_ops, stage_first_round=True, seed=seed)
    t0 = time.time()
    rep = loadgen.run(cfg)
    out = {
        "bench": "serve_headline",
        "rev": "r01",
        "host": "cpu",
        "at": round(t0, 1),
        # -- the headline ------------------------------------------------
        "sessions": rep["sessions"],
        "total_leaves": rep["leaves_acked"],
        "ops_merged": rep["ops_merged"],
        "sustained_ops_per_sec": rep["ops_per_sec"],
        "read_p50_ms": rep["read_p50_ms"],
        "read_p99_ms": rep["read_p99_ms"],
        "violations_total": rep["oracle"]["violations_total"],
        # -- the full report ---------------------------------------------
        "report": rep,
    }
    if out_path is None:
        out_path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_SERVE_r01_cpu.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=False)
        f.write("\n")
    return out


if __name__ == "__main__":
    argv = sys.argv[1:]
    kw = {}
    if argv:
        kw["n_sessions"] = int(argv[0])
    if len(argv) > 1:
        kw["writes_per_session"] = int(argv[1])
    if len(argv) > 2:
        kw["out_path"] = argv[2]
    out = run(**kw)
    print(json.dumps({k: v for k, v in out.items() if k != "report"},
                     indent=1), flush=True)
    rep = out["report"]
    if out["violations_total"] or rep["errors"]:
        print(f"FAIL: violations={out['violations_total']} "
              f"errors={rep['errors'][:3]}", file=sys.stderr)
        sys.exit(1)
    print("bench_serve_headline OK", file=sys.stderr)
