"""A/B the GRAFT_PACK_GATHER plane-row-gather layout on the live chip.

Runs the headline 1M merge (production exhaustive mode, fused order
check) twice — flag off, then flag on — each in a SUBPROCESS so the
trace-time flag cannot be shadowed by a cached trace.  Prints one JSON
line per leg.  Decision rule: if the packed leg is faster by more than
the repeat noise, flip the default in ops/merge.py (the layouts are
bit-identical, tests/test_merge_kernel.py).

Usage: python scripts/probe_packab.py [n_ops]
"""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

LEG = r"""
import json, os, sys
sys.path.insert(0, {repo!r})
if os.environ.get("JAX_PLATFORMS") == "cpu":
    # CPU smoke run: env and config both pin the CPU (utils/hostenv.py)
    from crdt_graph_tpu.utils import hostenv
    hostenv.scrub_tpu_env(1)
import jax
if os.environ.get("JAX_PLATFORMS") == "cpu":
    jax.config.update("jax_platforms", "cpu")
from crdt_graph_tpu.utils import compcache
compcache.enable()
jax.config.update("jax_enable_x64", True)
from crdt_graph_tpu.bench import runner, workloads
n = {n}
ops = workloads.chain_workload(64, n)
stats = runner.time_merge(ops, repeats=3, hints="exhaustive", audit=False,
                          expected_ts=workloads.chain_expected_ts(64, n))
stats["pack_gather"] = bool(os.environ.get("GRAFT_PACK_GATHER"))
print(json.dumps(stats), flush=True)
"""


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    repo = os.path.dirname(HERE)
    for flag in ("", "1"):
        env = dict(os.environ)
        env.pop("GRAFT_PACK_GATHER", None)
        if flag:
            env["GRAFT_PACK_GATHER"] = flag
        code = LEG.format(repo=repo, n=n)
        try:
            r = subprocess.run([sys.executable, "-c", code], env=env,
                               timeout=900, capture_output=True, text=True)
        except subprocess.TimeoutExpired:
            # a wedged leg must not lose the other one: record and go on
            print(json.dumps({"error": "leg timed out (900 s)",
                              "pack_gather": bool(flag)}), flush=True)
            continue
        # take the last stdout line that parses as a JSON OBJECT (banners,
        # bare scalars, or 'null' lines must not masquerade as the
        # result); a measured result survives even if the leg's teardown
        # then exits non-zero — chip time is too scarce to drop it
        result = None
        for line in reversed(r.stdout.strip().splitlines()):
            try:
                cand = json.loads(line)
            except ValueError:
                continue
            if isinstance(cand, dict):
                result = cand
                break
        if result is None:
            result = {"error": (r.stderr or r.stdout)[-400:],
                      "returncode": r.returncode,
                      "pack_gather": bool(flag)}
        elif r.returncode != 0:
            result["returncode"] = r.returncode
            result["teardown_stderr"] = (r.stderr or "")[-400:]
        print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
