"""A/B the round-7 fused kernel against the round-6 trace, same host.

Runs the headline 1M config-5 merge (production exhaustive mode, fused
order check) twice — all ``GRAFT_FUSED_*`` kill-switches OFF (the
round-6 kernel), then default-ON (the round-7 kernel) — each leg in a
SUBPROCESS so the trace-time flags cannot be shadowed by a cached
trace.  Prints one JSON line per leg plus a final ``verdict`` line with
the p50 ratio.  Works on any backend: the legs are device-tagged, and
the structural cuts (scatter-free run starts/compaction, host winner
election, single-weight rank pipeline) show on CPU exactly because
their lax fallbacks do less work — the ISSUE 3 acceptance asks for a
≥20 % same-host CPU p50 improvement (≥3 repeats each).

Usage: python scripts/probe_fusedab.py [n_ops] [repeats] [rounds]
(rounds default 2; use 1 on a chip — stable timing needs no interleaving)
"""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

FLAGS = ("GRAFT_FUSED_RESOLVE", "GRAFT_FUSED_TAIL", "GRAFT_FUSED_SCAN",
         "GRAFT_FUSED_SUPEROP")

LEG = r"""
import json, os, sys
sys.path.insert(0, {repo!r})
if os.environ.get("JAX_PLATFORMS") == "cpu":
    # CPU run: env and config both pin the CPU (utils/hostenv.py)
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
stats = runner.time_merge(ops, repeats={repeats}, hints="exhaustive",
                          audit=False,
                          expected_ts=workloads.chain_expected_ts(64, n))
stats["fused"] = os.environ.get("GRAFT_FUSED_RESOLVE", "1") != "0"
stats["device"] = jax.devices()[0].device_kind
print(json.dumps(stats), flush=True)
"""


def _run_leg(env, n, repeats):
    code = LEG.format(repo=os.path.dirname(HERE), n=n, repeats=repeats)
    try:
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           timeout=1200, capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        return {"error": "leg timed out (1200 s)"}
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
                  "returncode": r.returncode}
    elif r.returncode != 0:
        result["returncode"] = r.returncode
        result["teardown_stderr"] = (r.stderr or "")[-400:]
    return result


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    repeats = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    rounds = int(sys.argv[3]) if len(sys.argv) > 3 else 2
    # INTERLEAVED rounds (r6, r7, r6, r7, ...): same-host drift between
    # leg processes (page cache, thermal, co-tenants) measured ~15 % on
    # the driver box — alternating legs and taking each leg's best p50
    # cancels it instead of crediting or debiting it to the kernel
    legs = {False: [], True: []}
    for r in range(rounds):
        for fused in (False, True):
            env = dict(os.environ)
            for f in FLAGS:
                env.pop(f, None)
                if not fused:
                    env[f] = "0"
            result = _run_leg(env, n, repeats)
            result["leg"] = "r7-fused" if fused else "r6-baseline"
            result["round"] = r
            legs[fused].append(result)
            print(json.dumps(result), flush=True)
    best = {k: min((x["p50_ms"] for x in v if "p50_ms" in x),
                   default=None) for k, v in legs.items()}
    if best[False] and best[True]:
        old, new = best[False], best[True]
        dev = next((x.get("device") for x in legs[True]
                    if "device" in x), None)
        print(json.dumps({
            "verdict": "fused-vs-r6",
            "n_ops": n, "repeats": repeats, "rounds": rounds,
            "device": dev,
            "p50_ms_r6": old, "p50_ms_r7": new,
            "improvement": round(1.0 - new / old, 4),
            "meets_20pct": bool(new <= 0.8 * old),
        }), flush=True)


if __name__ == "__main__":
    main()
