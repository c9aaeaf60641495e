"""Pallas TPU kernel: bounded-span monotone gather (the P4 native layer).

``out[v, t] = values[v, rid[t]]`` where ``rid`` is NONDECREASING with
increments ≤ 1 — exactly the shape of the merge kernel's run-id
expansions (ops/merge.py step 12: ``run_fwd[rid]``, per-run weight
prefix ``a[rid]``, …).  XLA lowers these as generic random gathers over
the 2M-token axis; this kernel exploits the monotone structure instead:

- a tile of ``TILE`` tokens can only reference ``values`` rows in
  ``[rid[t0], rid[t0] + TILE]`` (increments ≤ 1), so each grid step DMAs
  one bounded slice HBM→VMEM, with the per-tile start offsets
  scalar-prefetched (``rid[::TILE]`` computed on device);
- the in-tile gather is an EXACT one-hot f32 matmul on the MXU
  (`(V, SPAN) × (SPAN, TILE)`): every value this kernel moves (token
  ids, weight prefix sums) is < 2^24, so float32 represents it exactly;
  the one-hot contraction sums exactly one term per output.

Numerical-safety guard: the wrapper refuses (falls back to lax) when any
input could reach 2^24.  The lax fallback (`_lax_gather`) is the
reference semantics; CPU/interpret tests pin kernel == fallback.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_mode

TILE = 1024      # tokens per grid step (matches XLA's s32[N] T(1024) layout)
SPAN = TILE + 128  # values rows DMA'd per tile (≥ TILE+128: aligned starts)

F24 = 1 << 24    # float32 exact-integer bound
KERNEL_NAME = "mono_gather"


def _lax_gather(values: jax.Array, rid: jax.Array) -> jax.Array:
    """Reference semantics: plain XLA gather."""
    return values[:, rid]


def _kernel(starts_ref, rid_ref, vals_hbm, out_ref, scratch, sem):
    i = pl.program_id(0)
    # starts arrive pre-divided by 128: multiplying back inside the
    # kernel lets Mosaic PROVE the dynamic DMA offset is 128-aligned
    # (an opaque prefetched scalar fails that proof)
    r0 = starts_ref[i] * 128
    copy = pltpu.make_async_copy(
        vals_hbm.at[:, pl.ds(r0, SPAN)], scratch, sem)
    copy.start()
    copy.wait()
    # off[t] = rid[t] - r0 ∈ [0, TILE+127] (starts floor to a lane
    # tile), which is why SPAN must be ≥ TILE+128; one-hot over SPAN
    off = rid_ref[...] - r0
    onehot = (off[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (TILE, SPAN), 1)).astype(jnp.float32)
    vals_f = scratch[...].astype(jnp.float32)          # [V, SPAN]
    # HIGHEST: the MXU's default bf16 passes truncate >2^8-magnitude
    # ints (caught live: 91158 read back as 91136); full-f32 passes
    # keep every product/sum exact below 2^24
    out = jax.lax.dot_general(
        vals_f, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)           # [V, TILE]
    out_ref[...] = out.astype(jnp.int32)


def _pallas_call(vals_pad, rid_pad, starts, v8, tiles, interpret):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles,),
        in_specs=[
            # rid rides 1-D: a (TILE,) block keeps the lane dim at a
            # multiple of 128 and matches XLA's s32[N] T(1024) layout
            # (Mosaic requires last-two block dims ≡ 0 mod (8, 128) or
            # full — a (1, TILE) block over [tiles, TILE] fails on
            # real TPU lowering; caught on first live-chip run)
            pl.BlockSpec((TILE,), lambda i, starts: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((v8, TILE), lambda i, starts: (0, i)),
        scratch_shapes=[
            pltpu.VMEM((v8, SPAN), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((v8, tiles * TILE), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
        name=KERNEL_NAME,
    )(starts, rid_pad, vals_pad)


def monotone_gather(values: jax.Array, rid: jax.Array,
                    use_pallas: bool | None = None,
                    interpret: bool = False) -> jax.Array:
    """``values[:, rid]`` for nondecreasing ``rid`` with increments ≤ 1.

    values: i32[V, R]; rid: i32[T].  Returns i32[V, T].
    ``use_pallas=None`` auto-selects: the Mosaic kernel on TPU backends,
    the lax gather elsewhere.  Falls back to lax whenever the exactness
    precondition (all magnitudes < 2^24) cannot be guaranteed from
    shapes alone.
    """
    v, r = values.shape
    t = rid.shape[0]
    run, interpret = pallas_mode.select(use_pallas, interpret)
    # shape-derived exactness guard: token ids < T, run values < R;
    # weights are bounded by T as well (prefix sums of 0/1 weights)
    if not run or max(r, t) >= F24 or v > 8:
        return _lax_gather(values, rid)

    tiles = -(-t // TILE)
    t_pad = tiles * TILE
    rid_pad = jnp.pad(rid.astype(jnp.int32), (0, t_pad - t), mode="edge")
    # DMA slices must be 8-aligned in the sublane dim: pad V up to 8
    v8 = -(-v // 8) * 8
    vals_pad = jnp.pad(values.astype(jnp.int32), ((0, v8 - v), (0, SPAN)))
    # Mosaic requires the dynamic lane-dim DMA offset to be 128-aligned:
    # each tile's start rounds down to a lane tile (the kernel multiplies
    # back); off ∈ [0, TILE+127] still < SPAN
    starts = rid_pad[::TILE] // 128
    # every operand is explicit i32; tracing the pallas_call itself under
    # x64 emits index/grid ops Mosaic cannot legalize ('func.func'), so
    # scope it to x32 — caller dtypes are unaffected (no-op when x64 is
    # already off)
    with jax.enable_x64(False):
        out = _pallas_call(vals_pad, rid_pad, starts, v8, tiles, interpret)
    return out[:v, :t]
