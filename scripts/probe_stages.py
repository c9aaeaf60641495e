"""Per-stage honest timing of the merge kernel on the current device.

Times the PRODUCTION trace truncated after each stage via the kernel's
own static ``probe`` cut points (ops/merge.py ``_materialize``/
``_finish``) — cumulative/nested, so consecutive differences apportion
device time per stage and XLA cannot DCE an earlier stage out of a
later cut.  The cuts live inside the kernel, so this can never drift
from it (the previous standalone mirror did, and over-reported the tour
stage by ~2×).  Each cut also pays its own checksum passes, so the
clean full kernel (stage 8, full-table fingerprint) can time below cut
7 — documented in docs/SHARD_TAIL.md §1.

Stages: 1 resolution | 2 frames+local validity | 3 cascade+cycles |
4 deletes+dead | 5 NSA+sibling sort+tour | 6 runs+Wyllie+expansion |
7 ranks+orders | 8 full kernel incl. statuses (no cuts).

Runs the bench's production configuration: hints="exhaustive",
host-checked no_deletes, chain workload.  ``profile()`` is the single
driver loop, so the on-chip and CPU profiles cannot diverge.

Usage: python scripts/probe_stages.py [N] [stage...]   (device = whatever
JAX selects; pin CPU by scrubbing the env first, see tests/conftest.py)
"""
import functools
import json
import os
import sys

sys.path.insert(0, "/root/repo")

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # CPU run: env and config both pin the CPU (utils/hostenv.py); a
    # CPU-intended profile must never hold the chip
    from crdt_graph_tpu.utils import hostenv
    hostenv.scrub_tpu_env(1)

import jax

if os.environ.get("JAX_PLATFORMS") == "cpu":
    jax.config.update("jax_platforms", "cpu")

from crdt_graph_tpu.utils import compcache
compcache.enable()
jax.config.update("jax_enable_x64", True)

from crdt_graph_tpu.bench import honest
from crdt_graph_tpu.bench.workloads import chain_workload
from crdt_graph_tpu.ops import merge as merge_mod


def profile(n: int = 1_000_000, stages=None, repeats: int = 3,
            log=lambda m: None, workload=None) -> list:
    """Stage-cut rows for a merge workload on the current device — the
    ONE timing driver shared by the CPU runs below and the TPU session's
    phases 7 (chain headline) and 8 (config-6 sub-cuts), so on-chip and
    CPU profiles cannot diverge.  ``workload`` defaults to the
    production 64-chain headline at ``n`` ops; ``stages`` may include
    the stage-5 sub-cuts 41/42/43 (ops/merge.py)."""
    stages = list(stages or range(1, 9))
    host_ops = workload if workload is not None else chain_workload(64, n)
    no_deletes = merge_mod.host_no_deletes(host_ops["kind"])
    ops = jax.device_put(host_ops)

    @functools.partial(jax.jit, static_argnums=(1,))
    def run(o, stage):
        if stage == 8:
            # the FULL NodeTable (not the narrower headline
            # fingerprint): stage 8 has no cuts and forces every output
            t = merge_mod._materialize(o, None, "exhaustive", no_deletes)
            return honest.fingerprint(t)
        return merge_mod._materialize(o, None, "exhaustive", no_deletes,
                                      stage)

    rows = []
    prev = 0.0
    for st in stages:
        s = honest.time_with_readback(run, ops, st, repeats=repeats)
        rows.append({"stage": st, "p50_ms": s["p50_ms"],
                     "delta_ms": round(s["p50_ms"] - prev, 1),
                     "compile_s": round(s["warm_ms"] / 1e3, 1)})
        log(f"stage {st}: p50 {s['p50_ms']:9.1f} ms   "
            f"delta {s['p50_ms'] - prev:9.1f} ms   "
            f"(compile+warm {s['warm_ms']/1e3:.1f}s)")
        prev = s["p50_ms"]
    return rows


def main():
    args = [int(a) for a in sys.argv[1:]]
    n = args[0] if args else 1_000_000
    stages = args[1:] or None
    dev = jax.devices()[0]
    rows = profile(n, stages, log=lambda m: print(m, flush=True))
    print(json.dumps({"metric": "merge_stage_profile", "n_ops": n,
                      "device": dev.platform,
                      "device_kind": dev.device_kind,
                      "mode": "exhaustive+no_deletes",
                      "stages": rows}), flush=True)


if __name__ == "__main__":
    main()
