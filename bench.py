"""Headline benchmark: the BASELINE.json north-star merge.

64 replicas' concurrent edits — 1M operations total — merged into one
converged tree by the batched semilattice join on one TPU chip.  Prints
ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "device": ...}

``vs_baseline`` is measured against the north-star target itself (1M ops in
100 ms ⇒ 10M ops/s, BASELINE.json `north_star`) since the reference
publishes no numbers (SURVEY §6): vs_baseline > 1 beats the target.

The workload is the adversarial-but-realistic concurrent shape: every
replica extends its own insertion chain (each add anchored at the replica's
previous add, chain heads anchored at the branch sentinel), so the merge
must interleave 64 chains of ~15.6k ops each under the RGA rule.
Correctness of this shape is pinned by the oracle-parity suites in tests/;
the full config sweep lives in ``python -m crdt_graph_tpu.bench``.

One process, and it fails (non-zero exit) when JAX finds no TPU: a
measurement never falls back to the CPU.  Progress streams to stderr.
"""
import json
import sys
import time

N_REPLICAS = 64
N_OPS = 1_000_000
TARGET_OPS_PER_S = 1e7  # north star: 1M ops < 100 ms


def main() -> None:
    import jax

    from crdt_graph_tpu.utils import compcache, hostenv
    compcache.enable()
    jax.config.update("jax_enable_x64", True)
    dev = hostenv.require_tpu("bench.py")[0]

    from crdt_graph_tpu.bench.runner import time_merge
    from crdt_graph_tpu.bench.workloads import chain_expected_ts, \
        chain_workload

    t0 = time.perf_counter()
    ops = chain_workload(N_REPLICAS, N_OPS)
    print(f"bench: workload generated in {time.perf_counter()-t0:.1f}s",
          file=sys.stderr, flush=True)
    print(f"bench: device {dev.device_kind} ({dev.platform})",
          file=sys.stderr, flush=True)
    # Order correctness at headline scale (VERDICT round 2, task 7) rides
    # the timed kernel itself: the converged VISIBLE SEQUENCE must equal
    # the closed-form greedy max-timestamp interleaving of the 64 chains,
    # element for element, checked on device in every repeat — a count
    # check alone would pass any all-adds identity mapping (and a second
    # full-kernel jit for the check would double TPU compile time).
    stats = time_merge(ops, repeats=5, progress=True,
                       expected_ts=chain_expected_ts(N_REPLICAS, N_OPS),
                       hints="exhaustive")
    assert stats["num_visible"] == stats["n_ops"], "merge dropped ops"
    assert stats["audit"]["ok"], \
        f"timing audit failed (async-dispatch lie): {stats['audit']}"
    assert stats["order_exact"], \
        "visible order deviates from closed-form expectation"
    print("bench: order check exact (closed-form 64-chain interleaving)",
          file=sys.stderr, flush=True)

    print(f"bench: stats {stats}", file=sys.stderr, flush=True)
    ops_per_s = stats["ops_per_sec"]
    print(json.dumps({
        "metric": "crdt_merge_throughput_64rep_1Mops",
        "value": ops_per_s,
        "unit": "ops/s",
        "vs_baseline": round(ops_per_s / TARGET_OPS_PER_S, 3),
        "device": dev.device_kind,
        "p50_ms": stats["p50_ms"],
        "order_check": "exact",
        "kernel_mode": "exhaustive (production mode for vouched "
                       "batches; order-checked against the closed form "
                       "in every timed repeat)",
        "audit": stats["audit"],
        "dispatch_overhead_ms": stats["dispatch_overhead_ms"],
        # trace-audit record (op count + width-weighted modeled ms +
        # budget verdict)
        "chain_audit": stats.get("chain_audit"),
        # ops-axis sharded-trace audit (ISSUE 13): per-shard width vs
        # the ceil(M/k)+halo budget, collective bytes, crowding leg
        "opsaxis": stats.get("opsaxis"),
    }), flush=True)


if __name__ == "__main__":
    main()
