"""Pallas TPU kernel: fused sequential prefix sums for the tour tail
(round 7; ISSUE 3 tentpole).

The merge kernel's rank pipeline needs independent 0/1-integer prefix
sums that XLA emits as separate M-wide serialized scan passes: the
run-id cumsum over the T = 2M Euler-tour boundary bits and the (1 or
2)-lane node-weight cumsums over the M slots (ops/merge.py step 12).
This kernel computes ALL of them in ONE pass: the lanes concatenate
into a single token stream (each segment padded to a tile multiple, so
segment starts are STATIC tile indices), and a sequential grid sweeps
it with an SMEM carry — TPU grid steps execute in order, so per-tile
partial sums turn into exact global prefixes, and the carry RESETS at
each segment's (static) first tile, keeping the segments' scans
independent.

The in-tile prefix runs on the MXU as one triangular one-hot matmul
per (8, 256) tile: every addend is 0/1 and a tile holds ≤ 2048 of
them, so the f32 contraction is exact (< 2^24); the int32 carry and
row offsets are added after the cast, keeping exactness for prefixes
up to 2^31.

``prefix_sums`` is the wrapper: the Mosaic kernel on TPU backends, the
same lax cumsums elsewhere — bit-identical either way
(tests/test_tour_scan.py).  ``GRAFT_NO_PALLAS=1`` forces the lax path
(ops/pallas_mode.select).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

TILE = 2048      # stream elements per grid step, as an (8, 256) block
KERNEL_NAME = "tour_scan_prefix"
ROWS, LANES = 8, 256


def _lax_prefix(boundary: jax.Array,
                weights: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Reference semantics: the round-6 lax scans (the run-id cumsum
    over T tokens, the batched weight cumsum over M)."""
    return lax.cumsum(boundary), lax.cumsum(weights, axis=1)


def _kernel(seg_starts, x_ref, o_ref, carry):
    """One (8, 256) tile: in-tile inclusive prefix + carry."""
    i = pl.program_id(0)
    # carry resets at each segment's static first tile
    reset = (i == seg_starts[0])
    for s in seg_starts[1:]:
        reset = reset | (i == s)

    @pl.when(reset)
    def _init():
        carry[0] = jnp.int32(0)

    x = x_ref[...].astype(jnp.float32)            # [8, 256] of 0/1
    tri = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) <=
           jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
           ).astype(jnp.float32)
    row_pref = jax.lax.dot_general(
        x, tri, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)      # [8, 256] incl.
    totals = row_pref[:, LANES - 1:LANES]         # [8, 1]
    # exclusive prefix over the 8 row totals (strict lower-tri)
    strict = (jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1) <
              jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
              ).astype(jnp.float32)
    offs = jax.lax.dot_general(
        strict, totals, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)      # [8, 1]
    pref = (row_pref + offs).astype(jnp.int32)
    o_ref[...] = pref + carry[0]
    # + tile total; summed in f32 (exact: ≤ 2048 addends of 0/1)
    # — an integer jnp.sum promotes to i64 under a global x64
    # config, which Mosaic cannot lower
    carry[0] = carry[0] + jnp.sum(x).astype(jnp.int32)


def _pallas_call(stream2d, seg_starts, tiles, interpret):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(tiles,),
        in_specs=[pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
    )
    # the "scan" in the name is LOAD-BEARING for the cost model:
    # utils/chainaudit bills sequential-scan kernels by their full
    # stream length (every element is serially swept), not by the
    # output row count like the bounded-span gather kernels
    return pl.pallas_call(
        functools.partial(_kernel, seg_starts),
        out_shape=jax.ShapeDtypeStruct(stream2d.shape, jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
        name=KERNEL_NAME,
    )(stream2d)


def _pad_tile(x: jax.Array) -> jax.Array:
    n = x.shape[0]
    return jnp.pad(x, (0, -n % TILE))


# ---- ops-axis sharded formulation (ISSUE 13) ---------------------------
#
# The same prefix sums with the token/slot axes SHARDED over a 1-D mesh
# axis: each device scans only its contiguous chunk (width ceil(M/k) —
# the per-shard width utils/chainaudit.py v3 bills), the per-chunk
# totals ride ONE fused ring exchange (lax.ppermute Hillis-Steele over
# the device axis — the "run-id offset + suffix-weight carry" exchange
# docs/SHARD_TAIL.md §4 items 1-2 designed), and a local elementwise
# fixup adds each chunk's exclusive carry.  Integer addition is
# associative and exact, so the sharded result is bit-identical to the
# single-device cumsum by construction.
#
# The T = 2M token axis splits as TWO ceil(M/k)-chunks per device (its
# enter-half chunk and its exit-half chunk) rather than one 2M/k chunk,
# so no billed op inside the shard body exceeds the M/k + halo budget.


def ring_exclusive(vals: jax.Array, axis: str, k: int,
                   op: str = "add") -> jax.Array:
    """Exclusive prefix of per-shard carry vectors around the mesh ring.

    ``vals`` is each device's [L]-lane local total; returns the [L]
    combine of all LOWER-indexed devices' totals (device 0: the
    identity).  log2(k)+1 ``lax.ppermute`` hops (Hillis-Steele
    inclusive, then one shift) — the carries are a handful of scalars,
    so latency, not bytes, prices this.  ``op="add"`` assumes identity
    0 (ppermute delivers zeros to devices with no sender); ``op="max"``
    requires the caller to BIAS values ≥ 1 so the zero-fill acts as the
    identity there too."""
    combine = jnp.maximum if op == "max" else (lambda a, b: a + b)
    incl = vals
    d = 1
    while d < k:
        shifted = lax.ppermute(incl, axis,
                               [(j, j + d) for j in range(k - d)])
        incl = combine(incl, shifted)
        d *= 2
    return lax.ppermute(incl, axis, [(j, j + 1) for j in range(k - 1)])


def sharded_prefix_sums(boundary: jax.Array, weights: jax.Array, *,
                        axis: str, k: int
                        ) -> Tuple[jax.Array, jax.Array]:
    """:func:`prefix_sums` semantics with every scan chunked to
    ceil(M/k) width per device and the carries ring-exchanged (module
    comment above).  Must run inside ``shard_map`` over ``axis`` with
    every operand REPLICATED; outputs are replicated (each device
    scans its own chunks, then one tiled all-gather reassembles)."""
    t = boundary.shape[0]
    kw, m = weights.shape
    w = -(-m // k)                      # chunk width = ceil(M/k)
    i = lax.axis_index(axis)
    b32 = boundary.astype(jnp.int32)
    w32 = weights.astype(jnp.int32)
    # token axis re-laid as [2, kW]: enter half then exit half, each
    # zero-padded to kW (zeros are cumsum identities, so padding between
    # the halves cannot change any real token's prefix)
    t_lo = min(m, t)
    ent = jnp.pad(b32[:t_lo], (0, k * w - t_lo))
    ext = jnp.pad(b32[t_lo:], (0, 2 * k * w - t))
    wp = jnp.pad(w32, ((0, 0), (0, k * w - m)))
    # local chunks: one dynamic_slice each (free), one W-wide cumsum each
    ca = lax.cumsum(lax.dynamic_slice(ent, (i * w,), (w,)))
    cb = lax.cumsum(lax.dynamic_slice(ext, (i * w,), (w,)))
    cw = lax.cumsum(lax.dynamic_slice(
        wp, (jnp.zeros((), i.dtype), i * w), (kw, w)), axis=1)
    # ONE fused ring exchange for every lane's carry: [2 + Kw] totals
    totals = jnp.concatenate([ca[-1:], cb[-1:], cw[:, -1]])
    ex = ring_exclusive(totals, axis, k)
    # the exit half's carry additionally folds the WHOLE enter half
    ent_total = lax.psum(ca[-1], axis)
    out_a = ca + ex[0]
    out_b = cb + ex[1] + ent_total
    out_w = cw + ex[2:][:, None]
    # reassemble replicated outputs (tiled all-gathers; the [2, W]
    # token pair interleaves back to chunk order elementwise)
    ab = lax.all_gather(jnp.stack([out_a, out_b]), axis,
                        tiled=False)                   # [k, 2, W]
    flat = jnp.transpose(ab, (1, 0, 2)).reshape(2 * k * w)
    ob = jnp.concatenate([flat[:t_lo], flat[k * w:k * w + (t - t_lo)]])
    wg = lax.all_gather(out_w, axis, tiled=False)      # [k, Kw, W]
    ow = jnp.transpose(wg, (1, 0, 2)).reshape(kw, k * w)[:, :m]
    return ob, ow


def prefix_sums(boundary: jax.Array, weights: jax.Array,
                use_pallas: bool | None = None,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Inclusive prefix sums ``(cumsum(boundary), cumsum(weights, 1))``
    for i32 ``boundary[T]`` and i32 ``weights[Kw, M]`` with every
    element in {0, 1}.  One fused pallas sweep on TPU backends; the lax
    cumsums elsewhere.  ``use_pallas`` follows the mono_gather
    convention (None = auto: Mosaic on TPU, lax elsewhere)."""
    t = boundary.shape[0]
    kw, m = weights.shape
    run, interpret = pallas_mode.select(use_pallas, interpret)
    if not run or kw > 3 or t < TILE:
        return _lax_prefix(boundary, weights)

    segs = [_pad_tile(boundary.astype(jnp.int32))] + \
        [_pad_tile(weights[k].astype(jnp.int32)) for k in range(kw)]
    starts, b = [], 0
    for s in segs:
        starts.append(b // TILE)
        b += s.shape[0]
    stream = jnp.concatenate(segs)
    tiles = stream.shape[0] // TILE
    with jax.enable_x64(False):
        out = _pallas_call(stream.reshape(tiles * ROWS, LANES),
                           tuple(starts), tiles, interpret)
    out = out.reshape(-1)
    ob = out[:t]
    t_pad = segs[0].shape[0]
    m_pad = segs[1].shape[0]
    ow = jnp.stack([out[t_pad + k * m_pad:t_pad + k * m_pad + m]
                    for k in range(kw)])
    return ob, ow
