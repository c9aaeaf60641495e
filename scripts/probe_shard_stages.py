"""Per-phase timing of the EXPLICIT shard schedule on a virtual mesh.

VERDICT r4 next-3 asks what config 5's "v5e-8 slice" actually buys for a
single 1M-op merge: the explicit schedule (parallel/shard.py) shards the
resolution stages and replicates the tail, so the measurable quantities
are

- ``resolve``: the shard_map'd resolution (slot scatter + pmin joins +
  summary all-gathers + distributed verification) — the part that
  SCALES with devices,
- ``full``: the whole shard_materialize — resolve + replicated tail,
- the single-device production kernel for reference.

The difference full − resolve is the replicated-tail share under the
explicit schedule; together with the single-chip stage profile
(scripts/probe_stages.py, kernel probe cuts) it feeds the scale-out
projection in docs/SHARD_TAIL.md.  CPU-mesh times are compute PROXIES
(collectives over shared memory are nearly free; real-ICI terms are
modeled separately in that doc), so the headline artifact is the SHARE,
not the wall-clock.

Usage: python scripts/probe_shard_stages.py [N] [n_devices]
"""
import functools
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

N_DEV = int(sys.argv[2]) if len(sys.argv) > 2 else 8
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           f" --xla_force_host_platform_device_count={N_DEV}")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from crdt_graph_tpu.bench import honest  # noqa: E402
from crdt_graph_tpu.bench.workloads import chain_workload  # noqa: E402
from crdt_graph_tpu.ops import merge as merge_mod  # noqa: E402
from crdt_graph_tpu.parallel import shard as shard_mod  # noqa: E402
from crdt_graph_tpu.parallel.mesh import OPS_AXIS, _pad_ops_to, round_up  # noqa: E402


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    mesh = Mesh(np.array(jax.devices()[:N_DEV]), (OPS_AXIS,))
    ops = chain_workload(64, n)
    no_deletes = merge_mod.host_no_deletes(np.asarray(ops["kind"]))
    padded = _pad_ops_to(ops, round_up(ops["kind"].shape[0], N_DEV))
    N = padded["kind"].shape[0]
    M = N + 2
    device_ops = {
        c: jax.device_put(
            padded[c],
            NamedSharding(mesh, P(OPS_AXIS) if padded[c].ndim == 1
                          else P(OPS_AXIS, None)))
        for c in shard_mod._COLS}
    args = [device_ops[c] for c in shard_mod._COLS]

    # --- resolve-only: the shard_map'd phase, checksum-forced
    body = functools.partial(shard_mod._resolve_local, N, M, False)
    resolve = jax.shard_map(body, mesh=mesh,
                            in_specs=tuple(
                                P(OPS_AXIS) if device_ops[c].ndim == 1
                                else P(OPS_AXIS, None)
                                for c in shard_mod._COLS),
                            out_specs=P(), check_vma=False)

    @jax.jit
    def resolve_only(*cols):
        gathered, sel, hints_ok = resolve(*cols)
        return honest.fingerprint(tuple(sel) + (hints_ok,))

    # --- full explicit-schedule merge (exhaustive mode: the production
    # path for vouched batches — matches the single-chip headline)
    @functools.partial(jax.jit, static_argnums=())
    def full(*cols):
        t = shard_mod._shard_materialize_jit(
            dict(zip(shard_mod._COLS, cols)), mesh, "exhaustive", None,
            no_deletes)
        return honest.fingerprint((t.doc_index, t.visible_order,
                                   t.status, t.ts))

    # --- single-device production kernel for reference
    single_ops = jax.device_put(padded)

    @jax.jit
    def single(o):
        t = merge_mod._materialize(o, None, "exhaustive", no_deletes)
        return honest.fingerprint((t.doc_index, t.visible_order,
                                   t.status, t.ts))

    rows = {}
    for name, fn, a in (("resolve_sharded", resolve_only, args),
                        ("full_sharded", full, args),
                        ("single_device", single, [single_ops])):
        s = honest.time_with_readback(fn, *a, repeats=3)
        rows[name] = s["p50_ms"]
        print(f"{name:16s} p50 {s['p50_ms']:9.1f} ms "
              f"(compile+warm {s['warm_ms']/1e3:.1f}s)", flush=True)

    tail = rows["full_sharded"] - rows["resolve_sharded"]
    print(json.dumps({
        "metric": "shard_stage_profile", "n_ops": n, "n_devices": N_DEV,
        "device": "cpu-mesh-proxy",
        "resolve_sharded_ms": rows["resolve_sharded"],
        "full_sharded_ms": rows["full_sharded"],
        "replicated_tail_ms": round(tail, 1),
        "tail_share": round(tail / rows["full_sharded"], 3),
        "single_device_ms": rows["single_device"],
    }), flush=True)


if __name__ == "__main__":
    main()
