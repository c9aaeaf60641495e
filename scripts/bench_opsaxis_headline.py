"""BENCH_OPSAXIS headline: honest interleaved A/B of the ops-axis
sharded merge vs the single-device kernel at the config-5 shape on the
8-device host-platform CPU mesh (ISSUE 13).

Two legs on the SAME padded arrays, interleaved per round (never
sequential blocks — box drift lands on both legs):

- ``sharded``: parallel/opsaxis.materialize — the shard_map kernel
  with halo-windowed plane sweeps, ring-carry scans, and all-reduce
  frame joins, every collective executing for real on the CPU mesh.
- ``single``: merge.materialize — the stock kernel.

Honest timing per repeat: dispatch + an 8-byte readback of a jitted
fingerprint scalar depending on every table field (bench/honest.py);
the two legs' fingerprints are asserted EQUAL first (bit-identity is
the contract the wall-clock rides on).

Read the result honestly (docs/SHARD_TAIL.md §2/§6 precedent): 8
virtual devices share this box's cores, so CPU-mesh wall-clock
measures the simulation, not the slice — the committed CLAIM is the
audited per-shard width (≤ ceil(M/8) + halo) and the collective-byte
count, both attached from utils/chainaudit v3; the wall-clock A/B is
committed either way as a broken-path tripwire (a hang, a pathological
fallback, or a silently-widened shard shows up here long before a chip
run would).  The on-chip check is ``python chip_smoke.py --chips 4``.

Usage: python scripts/bench_opsaxis_headline.py [n_ops] [repeats] [out]
"""
import json
import os
import platform
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from crdt_graph_tpu.utils import hostenv  # noqa: E402

hostenv.scrub_tpu_env(8)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from crdt_graph_tpu.bench import honest, workloads  # noqa: E402
from crdt_graph_tpu.codec import packed  # noqa: E402
from crdt_graph_tpu.ops import merge  # noqa: E402
from crdt_graph_tpu.parallel import opsaxis  # noqa: E402

# a sharded CPU-mesh leg slower than this multiple of the single-device
# leg is a broken path (hang / wholesale fallback / widened shard), not
# mesh-simulation overhead — the tripwire the slow test pins
TRIPWIRE_MAX_SLOWDOWN = 25.0


def _fingerprint_host(table) -> int:
    return int(np.asarray(jax.jit(honest.fingerprint)(table)))


def run(n_ops: int = 1_000_000, repeats: int = 3,
        out_path: str = "BENCH_OPSAXIS_r01_cpu.json") -> dict:
    k = opsaxis.mesh_devices()
    arrs = workloads.chain_workload(64, n_ops)
    n = arrs["kind"].shape[0]
    n_pad = -(-n // k) * k
    padded = packed.pad_arrays(arrs, n_pad) if n_pad != n else arrs

    legs = {
        "sharded": lambda: opsaxis.materialize(
            padded, k=k, hints="exhaustive"),
        "single": lambda: merge.materialize(padded,
                                            hints="exhaustive"),
    }
    # warm (compile) + bit-identity gate before any timing
    print("# warming + bit-identity check", file=sys.stderr)
    fps = {}
    for name, fn in legs.items():
        tab = fn()
        fps[name] = _fingerprint_host(tab)
    assert fps["sharded"] == fps["single"], \
        f"bit-identity violated: {fps}"

    times = {name: [] for name in legs}
    for r in range(repeats):
        for name, fn in legs.items():        # interleaved, not blocks
            t0 = time.perf_counter()
            tab = fn()
            fp = _fingerprint_host(tab)
            dt = (time.perf_counter() - t0) * 1e3
            assert fp == fps[name]
            times[name].append(round(dt, 1))
            print(f"# round {r} {name}: {dt:.1f} ms", file=sys.stderr)

    p50 = {name: float(np.percentile(ts, 50))
           for name, ts in times.items()}
    audit = opsaxis.audit_opsaxis(arrs)
    speedup = p50["single"] / p50["sharded"]
    out = {
        "bench": "opsaxis_headline_ab",
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime()),
        "config": "join_64rep_1M" if n_ops == 1_000_000
        else f"join_64rep_{n_ops}",
        "n_ops": int(n_pad),
        "devices": k,
        "host_cores": os.cpu_count(),
        "platform": platform.platform(),
        "interleaved": True,
        "repeats": repeats,
        "times_ms": times,
        "p50_ms": {name: round(v, 1) for name, v in p50.items()},
        "sharded_vs_single_speedup": round(speedup, 3),
        "bit_identical": True,
        "fingerprint": fps["single"],
        # the committed claim (the CPU wall-clock above is a
        # simulation-bound tripwire — module docstring)
        "opsaxis_audit": audit,
        "tripwire": {
            "max_slowdown": TRIPWIRE_MAX_SLOWDOWN,
            "ok": bool(speedup >= 1.0 / TRIPWIRE_MAX_SLOWDOWN),
        },
        "note": ("8 virtual devices share this host's cores: CPU-mesh "
                 "wall-clock measures the simulation (SHARD_TAIL.md "
                 "section 2/6 anti-correlation); the audited per-shard "
                 "width + collective bytes are the committed claim, "
                 "and the on-chip check is chip_smoke.py --chips 4"),
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out["p50_ms"] | {
        "speedup": out["sharded_vs_single_speedup"],
        "shard_width": audit["shard_width"],
        "collective_bytes": audit["collective_bytes"]}))
    return out


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    r = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    out = sys.argv[3] if len(sys.argv) > 3 else \
        "BENCH_OPSAXIS_r01_cpu.json"
    run(n, r, out)
