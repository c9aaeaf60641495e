"""Primitive-cost probe on the live chip: times each XLA building block
of the merge kernel at headline width, so per-stage blame is apportioned
from measured parts rather than guesses.

Honest timing: each repeat is dispatch + forced readback of a dependent
scalar (bench.honest); the per-call dispatch+readback floor is printed
first — subtract it mentally from every row.

Usage: python scripts/probe_prims.py [N] [FROM]   (default 1_000_000 0)
``FROM`` skips the first FROM rows — resume a probe list a chip call's
time limit cut short without re-paying the compiles of rows already
measured.
"""
import os
import sys
import time

sys.path.insert(0, "/root/repo")

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # CPU smoke run: env and config both pin the CPU (utils/hostenv.py)
    from crdt_graph_tpu.utils import hostenv
    hostenv.scrub_tpu_env(1)

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

if os.environ.get("JAX_PLATFORMS") == "cpu":
    jax.config.update("jax_platforms", "cpu")

from crdt_graph_tpu.utils import compcache
compcache.enable()
jax.config.update("jax_enable_x64", True)

from crdt_graph_tpu.bench import honest


_ROW_START = 0
_ROW_NUM = 0


def row(name, fn, *args, repeats=3):
    global _ROW_NUM
    _ROW_NUM += 1
    if _ROW_NUM <= _ROW_START:
        return None
    f = jax.jit(fn)
    s = honest.time_with_readback(f, *args, repeats=repeats)
    print(f"{name:34s} p50 {s['p50_ms']:8.1f} ms  min {s['min_ms']:8.1f}"
          f"  (warm {s['warm_ms']/1e3:.1f}s)", flush=True)
    return s["p50_ms"]


def main():
    global _ROW_START
    N = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    _ROW_START = int(sys.argv[2]) if len(sys.argv) > 2 else 0
    M = N + 2
    T = 2 * M
    rng = np.random.default_rng(0)

    i32a = jnp.asarray(rng.integers(0, N, N, dtype=np.int32))
    i32b = jnp.asarray(rng.integers(0, N, N, dtype=np.int32))
    i32c = jnp.asarray(rng.integers(0, N, N, dtype=np.int32))
    idxN = jnp.asarray(rng.integers(0, N, N, dtype=np.int32))
    idxT = jnp.asarray(rng.integers(0, T, T, dtype=np.int32))
    i32t = jnp.asarray(rng.integers(0, T, T, dtype=np.int32))
    i64N = jnp.asarray(rng.integers(0, 2**40, N, dtype=np.int64))

    fp = honest.fingerprint
    print(f"N={N}  floor={honest.overhead_floor_ms()} ms", flush=True)

    row("fingerprint(4xN i32) alone", lambda a: fp((a, a, a, a)), i32a)
    row("sort 2-key (3 arr) N", lambda a, b, c: fp(
        lax.sort((a, b, c), num_keys=2)), i32a, i32b, i32c)
    row("sort 3-key (3 arr) M~N", lambda a, b, c: fp(
        lax.sort((a, b, c), num_keys=3)), i32a, i32b, i32c)
    row("sort 1-key (1 arr) N", lambda a: fp(lax.sort((a,), num_keys=1)),
        i32a)
    row("sort 2-key i64-split N", lambda a: fp(
        lax.sort(((a >> 32).astype(jnp.int32),
                  (a & 0xFFFFFFFF).astype(jnp.int32) - 2**31,
                  jnp.arange(a.shape[0], dtype=jnp.int32)), num_keys=2)),
        i64N)
    row("cumsum T (=2M)", lambda a: fp(lax.cumsum(a)), i32t)
    row("cummax N", lambda a: fp(lax.cummax(a)), i32a)
    row("gather N<-N i32", lambda a, i: fp(a[i]), i32a, idxN)
    row("gather T<-T i32", lambda a, i: fp(a[i]), i32t, idxT)
    row("gather 7xT<-T i32", lambda a, i: fp(
        jnp.stack([a, a + 1, a + 2, a + 3, a + 4, a + 5, a + 6])[:, i]),
        i32t, idxT)
    row("scatter-set N i32 unique", lambda a, i: fp(
        jnp.zeros_like(a).at[i].set(a, mode="drop", unique_indices=True)),
        i32a, idxN)
    row("scatter-set N i32 dup-safe", lambda a, i: fp(
        jnp.zeros_like(a).at[i].set(a, mode="drop")), i32a, idxN)
    row("scatter-min N i32", lambda a, i: fp(
        jnp.full_like(a, 2**31 - 1).at[i].min(a, mode="drop")), i32a, idxN)
    row("while_loop 10x (gather N)", lambda a, i: fp(
        lax.while_loop(lambda s: s[1] < 10,
                       lambda s: (s[0][i], s[1] + 1), (a, jnp.int32(0)))),
        i32a, idxN)
    row("gather i64 N", lambda a, i: fp(a[i]), i64N, idxN)
    row("searchsorted 4N in N (sort)", lambda a, q: fp(
        jnp.searchsorted(a, q, method="sort")),
        jnp.sort(i64N), jnp.concatenate([i64N, i64N, i64N, i64N]))
    # ---- hint-resolution layout candidates (stage 1 = 270 ms on-chip:
    # which of these dominates decides the next rewrite)
    row("gather 2xN sep i32 same idx", lambda a, b, i: fp((a[i], b[i])),
        i32a, i32b, idxN)
    row("gather i64-as-2xi32 halves N", lambda a, i: fp(
        ((a >> 32).astype(jnp.int32)[i],
         (a & 0xFFFFFFFF).astype(jnp.int32)[i])), i64N, idxN)
    row("gather stack[3,N] col i32", lambda a, b, c, i: fp(
        jnp.stack([a, b, c])[:, i]), i32a, i32b, i32c, idxN)
    row("gather stack[N,3] row i32", lambda a, b, c, i: fp(
        jnp.stack([a, b, c], axis=-1)[i]), i32a, i32b, i32c, idxN)
    row("gather [N,8] i64 plane row", lambda p, i: fp(p[i]),
        jnp.tile(i64N[:, None], (1, 8)), idxN)
    row("scatter-set M i32 (drop)", lambda a, i: fp(
        jnp.zeros(a.shape[0] + 2, jnp.int32).at[i].set(
            a, mode="drop", unique_indices=True)), i32a, idxN)
    row("scatter [N,8] i32 plane", lambda v, i: fp(
        jnp.zeros((v.shape[0] + 2, 8), jnp.int32).at[i].set(
            jnp.tile(v[:, None], (1, 8)), mode="drop",
            unique_indices=True)), i32a, idxN)
    row("reduction sum 4xN i32", lambda a: fp(
        (jnp.sum(a), jnp.sum(a * 2), jnp.sum(a ^ 3), jnp.max(a))), i32a)
    # ---- per-HLO fixed overhead vs width (rows 25-27): the 1M rows put
    # every primitive at ~6 ms; if a 32k gather costs the SAME, the cost
    # is per-op dispatch/serialization, not throughput — then the
    # run-compacted tour loops (R_CAP=32k x ~15 Wyllie rounds) price
    # like full-width ops and chain LENGTH is the only lever anywhere.
    K = 32_768
    i32k = jnp.asarray(rng.integers(0, K, K, dtype=np.int32))
    idxK = jnp.asarray(rng.integers(0, K, K, dtype=np.int32))
    row("gather 32k<-32k i32", lambda a, i: fp(a[i]), i32k, idxK)
    row("while_loop 10x (gather 32k)", lambda a, i: fp(
        lax.while_loop(lambda c: c[0] < 10,
                       lambda c: (c[0] + 1, c[1][i]),
                       (jnp.int32(0), a))[1]), i32k, idxK)
    row("20x dependent elementwise N", lambda a: fp(
        _chain_elementwise(a, 20)), i32a)
    # ---- round-6 fused-resolution layouts (rows 28-31): the exact
    # shapes the restructured kernel ships (ops/merge.py fused path,
    # chain budget utils/chainaudit.py) — price each against the
    # single-primitive rows above to confirm the ≤16-op model's
    # assumption that one packed pass costs ~one pass.
    plane5 = jnp.tile(i64N[:, None], (1, 5))
    row("gather [N,5] i64 fused plane", lambda p, i: fp(p[i]),
        plane5, idxN)
    S = 65_536
    row("scatter [64k,2] i32 packed (N idx)", lambda v, i: fp(
        jnp.full((S, 2), 2**31 - 1, jnp.int32).at[
            jnp.where(i < S, i, S)].set(
            jnp.stack([v, v ^ 5], -1), mode="drop",
            unique_indices=True)), i32a, idxN)
    row("cumsum [2,N] batched", lambda a: fp(
        lax.cumsum(jnp.stack([a, a ^ 3]), axis=1)), i32a)
    # near-diagonal index (the production nsr shape: rank order ==
    # array order ± jitter) so the bounded-span kernel path, not its
    # lax fallback, is what gets priced
    diag = jnp.clip(jnp.arange(N, dtype=jnp.int32) + (idxN % 97) - 48,
                    0, N - 1)
    row("pallas span_row_gather [N,5] i64", lambda p, i: fp(
        _span_rows(p, i)), plane5, diag)
    # ---- round-7 fused shapes (rows 32-34): the exact kernels the
    # ≤10-op chain ships (docs/TPU_PROFILE.md §8) — price each against
    # its unfused equivalent above to confirm one pallas superop costs
    # ~one serialized pass.
    plane6h = jnp.concatenate(
        [jnp.tile(i64N[:, None], (1, 4)),
         jnp.clip(jnp.arange(N, dtype=jnp.int64) + (idxN % 97) - 48,
                  0, N - 1)[:, None],          # near-diagonal hop col
         i64N[:, None]], axis=1)
    row("pallas plane_rows2 2hop [N,6] i64", lambda p, i: fp(
        _span_rows2(p, i)), plane6h, diag)
    bnd = jnp.asarray(rng.integers(0, 2, T, dtype=np.int32))
    wts = jnp.asarray(rng.integers(0, 2, (1, N + 2), dtype=np.int32))
    row("pallas tour_scan T+M prefix", lambda b, w: fp(
        _tour_scan(b, w)), bnd[:2 * (N + 2)], wts)
    ridq = jnp.sort(jnp.asarray(
        rng.integers(0, 4096, T, dtype=np.int32)))
    row("searchsorted 4k in T unrolled", lambda r, k: fp(
        jnp.searchsorted(r, k, side="left", method="scan_unrolled")),
        ridq, jnp.arange(4096, dtype=jnp.int32))


def _span_rows(p, i):
    from crdt_graph_tpu.ops import fused_resolve
    return fused_resolve.plane_rows(p, i)


def _span_rows2(p, i):
    from crdt_graph_tpu.ops import fused_resolve
    return fused_resolve.plane_rows2(p, i, 4)


def _tour_scan(b, w):
    from crdt_graph_tpu.ops import tour_scan
    return tour_scan.prefix_sums(b, w)


def _chain_elementwise(a, k):
    """k strictly dependent full-width elementwise passes (rotations mix
    lanes so XLA cannot fold the chain into one op)."""
    for j in range(k):
        a = jnp.roll(a, 1) ^ (a + jnp.int32(2 * j + 1))
    return a


if __name__ == "__main__":
    main()
