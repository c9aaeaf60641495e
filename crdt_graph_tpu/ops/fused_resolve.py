"""Pallas TPU kernel: bounded-span multi-column row gather — the fused
node-frame resolution sweep (VERDICT r5 next-1b).

``out[t, :] = plane[idx[t], :]`` for an int64 plane whose indices are
ARBITRARY per element but LOCALLY bounded: within each ``TILE`` of
outputs the indices fall inside a ``SPAN``-row window.  This
generalizes ops/mono_gather.py (which requires a nondecreasing index
with increments ≤ 1) to the merge kernel's node-frame gather, whose
index is the canonical-source-row column ``nsr``: near-diagonal
whenever the batch arrives in (near-)timestamp order — the serving
shape, and the config-5 headline exactly (replica-blocked generation
makes rank order equal array order) — and arbitrary for shuffled
deliveries, which take the fallback.

Same scaffold as the validated mono_gather kernel: one bounded slice
DMA'd HBM→VMEM per grid step with scalar-prefetched 128-aligned start
offsets, and an EXACT one-hot MXU contraction.  Two generalizations:

- the per-tile start is the tile's MINIMUM index (a cheap on-device
  reshape-min), not ``rid[t0]``: in-tile offsets may land anywhere in
  ``[0, SPAN)``, in any order;
- int64 values travel as FOUR 16-bit limbs: every limb < 2^16 is
  exactly representable in float32, so the one-hot matmul is exact for
  the FULL int64 range and mono_gather's < 2^24 magnitude guard
  disappears; limbs repack elementwise after the kernel.

A tile whose indices straddle more than ``SPAN`` rows fails the
on-device span check, and ``lax.cond`` selects the lax gather INSIDE
the trace — fragmented batches cost the fallback's speed, never
correctness.  ``_lax_rows`` is the reference semantics; CPU/interpret
bit-identity (including the full merge) is pinned by
tests/test_fused_resolve.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

TILE = 1024        # output rows per grid step
SPAN = TILE + 128  # plane rows DMA'd per tile (starts floor to 128)
MAX_LANES = 512    # widest limb plane worth staging through VMEM
KERNEL_NAME = "fused_resolve_rows"     # plane_rows
KERNEL_NAME2 = "fused_resolve_rows2"   # plane_rows2


def _lax_rows(plane: jax.Array, idx: jax.Array) -> jax.Array:
    """Reference semantics: plain XLA row gather."""
    return plane[idx]


def _limb_plane(plane: jax.Array, span: int) -> jax.Array:
    """int64 ``[R, C]`` → i32 ``[R + pad, 4C → 128k]`` 16-bit limbs
    (exact in f32).  Lanes pad to a multiple of 128: Mosaic refuses a
    DMA window whose lane width is not tile-aligned, and XLA's HBM
    layout pads the minor dim to 128 anyway, so the padding costs no
    memory.  Rows pad so the last tile's ``span``-window DMA stays in
    bounds."""
    r, c = plane.shape
    limbs = jnp.stack(
        [((plane >> s) & 0xFFFF).astype(jnp.int32)
         for s in (0, 16, 32, 48)], axis=-1).reshape(r, 4 * c)
    return jnp.pad(limbs, ((0, span + (-r) % 8), (0, -(4 * c) % 128)))


def _repack(o: jax.Array, t: int, c: int) -> jax.Array:
    """Kernel output limbs ``[T_pad, 128k]`` → int64 ``[t, c]``."""
    v = o[:t, :4 * c].astype(jnp.int64).reshape(t, c, 4)
    return (v[:, :, 0] | (v[:, :, 1] << 16) |
            (v[:, :, 2] << 32) | (v[:, :, 3] << 48))


def _kernel(starts_ref, idx_ref, plane_hbm, out_ref, scratch, sem):
    i = pl.program_id(0)
    # starts arrive pre-divided by 128: multiplying back inside the
    # kernel lets Mosaic PROVE the dynamic DMA offset is aligned
    # (an opaque prefetched scalar fails that proof) — the same
    # trick as mono_gather, applied to the SUBLANE (row) dim
    r0 = starts_ref[i] * 128
    copy = pltpu.make_async_copy(
        plane_hbm.at[pl.ds(r0, SPAN), :], scratch, sem)
    copy.start()
    copy.wait()
    off = idx_ref[...] - r0            # [TILE] ∈ [0, SPAN)
    onehot = (off[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (TILE, SPAN), 1)).astype(jnp.float32)
    vals_f = scratch[...].astype(jnp.float32)          # [SPAN, C4]
    # full-f32 MXU passes: every operand is a 16-bit limb < 2^16,
    # products/sums stay below 2^24 — exact (mono_gather's guard
    # bound, satisfied by construction here)
    out = jax.lax.dot_general(
        onehot, vals_f, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)           # [TILE, C4]
    out_ref[...] = out.astype(jnp.int32)


def _pallas_call(limbs_pad, idx_pad, starts, c4, tiles, interpret):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles,),
        in_specs=[
            # idx rides 1-D (TILE,) blocks — lane dim multiple of
            # 128, matching XLA's s32[N] layout (mono_gather note)
            pl.BlockSpec((TILE,), lambda i, starts: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((TILE, c4), lambda i, starts: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((SPAN, c4), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((tiles * TILE, c4), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
        name=KERNEL_NAME,
    )(starts, idx_pad, limbs_pad)


HOP_J = 1152       # 2nd-hop locality bound, rounded to a lane tile
SPAN2 = SPAN + 2 * HOP_J   # 2nd-hop window rows per tile


def halo_window_ok(idx: jax.Array, w: int, halo: int,
                   nrows: int) -> jax.Array:
    """The ops-axis halo twin of this module's per-tile span checks
    (parallel/opsaxis.py): output row j belongs to shard j // w, whose
    plane window is ``[shard_lo - halo, shard_lo + w + halo)``; rows 0
    and nrows-1 (ROOT/NULL frames) are overlaid elementwise by the
    windowed gather and therefore exempt.  Replicated scalar — every
    device evaluates the same predicate, so the ``lax.cond`` fallback
    to the single-device gather stays uniform across the mesh."""
    own_lo = (jnp.arange(idx.shape[0], dtype=jnp.int32) //
              jnp.int32(w)) * jnp.int32(w)
    exempt = (idx <= 0) | (idx >= nrows - 1)
    in_win = (idx >= own_lo - halo) & (idx < own_lo + w + halo)
    return jnp.all(exempt | in_win)


def _kernel2(starts_ref, idx_ref, plane_hbm, out_ref, out2_ref,
             scr_a, scr_b, sem_a, sem_b, *, hop_col, r_rows):
    """Two dependent bounded-span row gathers in one VMEM pass: the
    first hop exactly as :func:`_kernel`; the hop index then
    re-packs from the gathered row's ``hop_col`` limbs IN REGISTER
    and drives a second one-hot contraction over a wider window
    whose start derives from the first (the HOP_J locality bound
    the wrapper verified)."""
    i = pl.program_id(0)
    r0 = starts_ref[i] * 128
    rb = starts_ref[pl.num_programs(0) + i] * 128
    ca = pltpu.make_async_copy(
        plane_hbm.at[pl.ds(r0, SPAN), :], scr_a, sem_a)
    ca.start()
    cb = pltpu.make_async_copy(
        plane_hbm.at[pl.ds(rb, SPAN2), :], scr_b, sem_b)
    cb.start()
    ca.wait()
    off = idx_ref[...] - r0            # [TILE] ∈ [0, SPAN)
    onehot = (off[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (TILE, SPAN), 1)).astype(jnp.float32)
    vals_a = scr_a[...].astype(jnp.float32)        # [SPAN, C4]
    g = jax.lax.dot_general(
        onehot, vals_a, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
    out_ref[...] = g
    # hop index from the gathered row's limb pair (i32 bit-exact:
    # the hop column stores row indices < 2^31, or -1)
    hop = (g[:, 4 * hop_col + 1] << 16) | g[:, 4 * hop_col]
    valid2 = hop >= 0
    i2 = jnp.clip(hop, jnp.int32(0), jnp.int32(r_rows - 1))
    off2 = i2 - rb
    cb.wait()
    onehot2 = ((off2[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (TILE, SPAN2), 1)) &
        valid2[:, None]).astype(jnp.float32)
    vals_b = scr_b[...].astype(jnp.float32)        # [SPAN2, C4]
    out2_ref[...] = jax.lax.dot_general(
        onehot2, vals_b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)


def _pallas_call2(limbs_pad, idx_pad, starts2, c4, tiles, hop_col,
                  r_rows, interpret):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((TILE,), lambda i, starts: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((TILE, c4), lambda i, starts: (i, 0)),
            pl.BlockSpec((TILE, c4), lambda i, starts: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((SPAN, c4), jnp.int32),
            pltpu.VMEM((SPAN2, c4), jnp.int32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel2, hop_col=hop_col, r_rows=r_rows),
        out_shape=[
            jax.ShapeDtypeStruct((tiles * TILE, c4), jnp.int32),
            jax.ShapeDtypeStruct((tiles * TILE, c4), jnp.int32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
        name=KERNEL_NAME2,
    )(starts2, idx_pad, limbs_pad)


def _lax_rows2(plane: jax.Array, idx: jax.Array, hop_col: int):
    """Reference semantics of the 2-hop sweep: ``g = plane[idx]``, then
    ``g2 = plane[clip(hop, 0, R-1)]`` where ``hop = g[:, hop_col]``,
    ZEROED where the hop is negative (no parent row)."""
    g = _lax_rows(plane, idx)
    hop = g[:, hop_col]
    i2 = jnp.clip(hop, 0, plane.shape[0] - 1).astype(jnp.int32)
    g2 = jnp.where((hop >= 0)[:, None], _lax_rows(plane, i2), 0)
    return g, g2


def plane_rows2(plane: jax.Array, idx: jax.Array, hop_col: int,
                use_pallas: bool | None = None,
                interpret: bool = False):
    """The 2-hop node-frame sweep (round 7 resolution superop):
    ``g = plane[idx]`` and ``g2 = plane[clip(g[:, hop_col], 0, R-1)]``
    (zeroed rows where the hop is negative), with BOTH dependent
    gathers in one pallas VMEM pass on TPU.

    The second window's start derives from the first (no data-dependent
    prefetch): legal iff the hop column is LOCALLY bounded —
    ``|plane[j, hop_col] - j| <= HOP_J - 128`` for every row whose hop
    is nonnegative (elementwise, checked in-trace).  A violating batch
    takes the single-hop pallas sweep + a lax second gather via
    ``lax.cond`` — fallback speed, never correctness.  Bit-identity
    incl. the fallback split is pinned by tests/test_fused_resolve.py.
    """
    r, c = plane.shape
    t = idx.shape[0]
    c4 = 4 * c
    run, interpret = pallas_mode.select(use_pallas, interpret)
    if not run or plane.dtype != jnp.int64 or c4 > MAX_LANES:
        return _lax_rows2(plane, idx, hop_col)

    tiles = -(-t // TILE)
    t_pad = tiles * TILE
    idx_pad = jnp.pad(idx.astype(jnp.int32), (0, t_pad - t), mode="edge")
    by_tile = idx_pad.reshape(tiles, TILE)
    starts = jnp.min(by_tile, axis=1) // 128
    span_ok = jnp.all(jnp.max(by_tile, axis=1) - starts * 128 <
                      jnp.int32(SPAN))
    # hop locality: every nonnegative hop stays within HOP_J - 128 of
    # its own plane row, so window B = [128·startA - HOP_J, ...+SPAN2)
    # covers every reachable hop (start floors eat up to 127 rows)
    hops = plane[:, hop_col]
    rows_iota = jnp.arange(r, dtype=jnp.int64)
    hop_ok = jnp.all((hops < 0) |
                     (jnp.abs(hops - rows_iota) <= HOP_J - 128))
    starts2 = jnp.maximum(starts - HOP_J // 128, 0)
    both = jnp.concatenate([starts, starts2])

    def _pallas2(_):
        limbs_pad = _limb_plane(plane, SPAN2)
        with jax.enable_x64(False):
            o1, o2 = _pallas_call2(limbs_pad, idx_pad, both,
                                   limbs_pad.shape[1], tiles, hop_col, r,
                                   interpret)
        return _repack(o1, t, c), _repack(o2, t, c)

    def _hop1(_):
        # hop locality violated (or fragmented): first hop keeps its
        # own bounded-span pallas sweep, second hop is the lax gather
        g = plane_rows(plane, idx, use_pallas=True, interpret=interpret)
        hop = g[:, hop_col]
        i2 = jnp.clip(hop, 0, r - 1).astype(jnp.int32)
        g2 = jnp.where((hop >= 0)[:, None], _lax_rows(plane, i2), 0)
        return g, g2

    return lax.cond(span_ok & hop_ok, _pallas2, _hop1, None)


def plane_rows(plane: jax.Array, idx: jax.Array,
               use_pallas: bool | None = None,
               interpret: bool = False) -> jax.Array:
    """``plane[idx]`` for an i64 ``plane[R, C]`` and i32 ``idx[T]`` with
    ``0 <= idx < R``.  ``use_pallas=None`` auto-selects: the Mosaic
    kernel on TPU backends (with an in-trace span-check fallback to
    lax), the lax gather elsewhere; falls back outright when the limb
    plane would be too wide to stage through VMEM."""
    r, c = plane.shape
    t = idx.shape[0]
    c4 = 4 * c
    run, interpret = pallas_mode.select(use_pallas, interpret)
    if not run or plane.dtype != jnp.int64 or c4 > MAX_LANES:
        return _lax_rows(plane, idx)

    tiles = -(-t // TILE)
    t_pad = tiles * TILE
    idx_pad = jnp.pad(idx.astype(jnp.int32), (0, t_pad - t), mode="edge")
    by_tile = idx_pad.reshape(tiles, TILE)
    starts = jnp.min(by_tile, axis=1) // 128
    # every tile's window [128·start, 128·start + SPAN) must cover its
    # indices; a violating tile routes the WHOLE gather to lax (one
    # cond, not per-row patching — fragmented batches are wholesale
    # fallback shapes, not mostly-local ones)
    span_ok = jnp.all(jnp.max(by_tile, axis=1) - starts * 128 <
                      jnp.int32(SPAN))

    def _pallas(_):
        limbs_pad = _limb_plane(plane, SPAN)
        # every operand is explicit i32; trace the call under x32 like
        # mono_gather (x64 tracing emits grid ops Mosaic cannot
        # legalize) — caller dtypes are unaffected
        with jax.enable_x64(False):
            out = _pallas_call(limbs_pad, idx_pad, starts,
                               limbs_pad.shape[1], tiles, interpret)
        return _repack(out, t, c)

    return lax.cond(span_ok, _pallas, lambda _: _lax_rows(plane, idx),
                    None)
