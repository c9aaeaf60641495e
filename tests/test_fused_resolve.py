"""The pallas bounded-span row gather must equal the lax reference
(ops/fused_resolve.py), and the full merge must be bit-identical with
the pallas resolution path green — across every sweep shape, including
the three adversarial configs (ISSUE 2 satellite: fused-resolution
coverage).  Runs the Mosaic kernel in interpreter mode on CPU; the
v5e compile is tests/test_tpu_compile.py, the chip run chip_smoke.py."""
import numpy as np
import pytest

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from crdt_graph_tpu.bench import workloads  # noqa: E402
from crdt_graph_tpu.codec import packed  # noqa: E402
from crdt_graph_tpu.ops import fused_resolve, merge, view  # noqa: E402

FIELDS = ["ts", "parent", "depth", "value_ref", "paths", "exists",
          "tombstone", "dead", "visible", "doc_index", "order",
          "visible_order", "num_nodes", "num_visible", "status"]


def _bounded_span_idx(rng, t, r, spread):
    """Indices that wander but stay within ``spread`` of a moving base
    (bounded span per tile when spread is small)."""
    base = np.minimum(np.arange(t, dtype=np.int64) * max(r - 1, 1) // max(t, 1),
                      r - 1)
    jitter = rng.integers(-spread, spread + 1, t)
    return np.clip(base + jitter, 0, r - 1).astype(np.int32)


@pytest.mark.parametrize("t,r,c", [(7, 5, 1), (700, 700, 3),
                                   (1024, 4096, 5), (2050, 2050, 9)])
def test_interpret_matches_lax(t, r, c):
    rng = np.random.default_rng(t * 31 + r)
    # full int64 range including >= 2^48 values (timestamps): the
    # 16-bit-limb one-hot contraction must be exact everywhere
    plane = rng.integers(0, 2**62, (r, c), dtype=np.int64)
    plane[rng.random((r, c)) < 0.2] = 2**62 - 1
    idx = _bounded_span_idx(rng, t, r, spread=40)
    want = np.asarray(fused_resolve._lax_rows(jnp.asarray(plane),
                                              jnp.asarray(idx)))
    got = np.asarray(fused_resolve.plane_rows(
        jnp.asarray(plane), jnp.asarray(idx), interpret=True))
    np.testing.assert_array_equal(got, want)


def test_constant_and_identity_idx():
    plane = jnp.asarray(
        np.arange(40, dtype=np.int64).reshape(8, 5) * 3**30)
    for idx in (np.zeros(1300, np.int32),
                np.arange(8, dtype=np.int32)):
        got = np.asarray(fused_resolve.plane_rows(
            plane, jnp.asarray(idx), interpret=True))
        np.testing.assert_array_equal(
            got, np.asarray(plane)[np.asarray(idx)])


def test_span_violation_falls_back_identically():
    """A shuffled index (no bounded span) must take the in-trace lax
    fallback and still be exactly right."""
    rng = np.random.default_rng(0)
    r, t, c = 8192, 2048, 4
    plane = rng.integers(0, 2**62, (r, c), dtype=np.int64)
    idx = rng.permutation(r)[:t].astype(np.int32)   # spans ~all rows
    got = np.asarray(fused_resolve.plane_rows(
        jnp.asarray(plane), jnp.asarray(idx), interpret=True))
    np.testing.assert_array_equal(got, np.asarray(plane)[idx])


def test_auto_falls_back_on_cpu():
    rng = np.random.default_rng(1)
    plane = jnp.asarray(rng.integers(0, 2**40, (300, 3), dtype=np.int64))
    idx = jnp.asarray(_bounded_span_idx(rng, 200, 300, 10))
    got = np.asarray(fused_resolve.plane_rows(plane, idx))
    np.testing.assert_array_equal(got, np.asarray(plane)[np.asarray(idx)])


# --- the 2-hop resolution superop (round 7) ---------------------------

def _hop_plane(rng, r, c, hop_col, hop_spread, neg_frac=0.2):
    """A plane whose ``hop_col`` holds a locally-bounded row index
    (or -1 with probability ``neg_frac``) and full-range int64 payload
    elsewhere."""
    plane = rng.integers(0, 2**62, (r, c), dtype=np.int64)
    hops = np.clip(np.arange(r) +
                   rng.integers(-hop_spread, hop_spread + 1, r), 0, r - 1)
    hops[rng.random(r) < neg_frac] = -1
    plane[:, hop_col] = hops
    return plane


@pytest.mark.parametrize("t,r,c,hop_col", [
    (700, 700, 3, 1), (1024, 4096, 5, 2), (2050, 2050, 6, 4)])
def test_plane_rows2_interpret_matches_lax(t, r, c, hop_col):
    rng = np.random.default_rng(t * 13 + r)
    plane = _hop_plane(rng, r, c, hop_col, hop_spread=40)
    idx = _bounded_span_idx(rng, t, r, spread=40)
    want = fused_resolve._lax_rows2(jnp.asarray(plane),
                                    jnp.asarray(idx), hop_col)
    got = fused_resolve.plane_rows2(jnp.asarray(plane),
                                    jnp.asarray(idx), hop_col,
                                    interpret=True)
    for gw, ww, tag in ((got[0], want[0], "hop1"),
                        (got[1], want[1], "hop2")):
        np.testing.assert_array_equal(np.asarray(gw), np.asarray(ww),
                                      err_msg=tag)


def test_plane_rows2_span_violation_falls_back():
    """A shuffled FIRST-hop index routes the whole sweep through the
    fallback branch — still exactly right."""
    rng = np.random.default_rng(2)
    r, t, c = 8192, 2048, 4
    plane = _hop_plane(rng, r, c, 2, hop_spread=30)
    idx = rng.permutation(r)[:t].astype(np.int32)
    want = fused_resolve._lax_rows2(jnp.asarray(plane),
                                    jnp.asarray(idx), 2)
    got = fused_resolve.plane_rows2(jnp.asarray(plane),
                                    jnp.asarray(idx), 2, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_plane_rows2_hop_violation_falls_back():
    """A far-jumping SECOND hop (|hop - row| > HOP_J) keeps the first
    hop on the single-hop pallas sweep and takes the lax second gather
    — still exactly right."""
    rng = np.random.default_rng(3)
    r, t, c = 8192, 2048, 4
    plane = _hop_plane(rng, r, c, 2, hop_spread=30)
    hops = np.asarray(plane[:, 2]).copy()
    hops[100] = r - 1                      # one violating far hop
    plane[:, 2] = hops
    idx = _bounded_span_idx(rng, t, r, spread=40)
    want = fused_resolve._lax_rows2(jnp.asarray(plane),
                                    jnp.asarray(idx), 2)
    got = fused_resolve.plane_rows2(jnp.asarray(plane),
                                    jnp.asarray(idx), 2, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# --- full-merge parity: every sweep shape, pallas resolution green ----

def _small_configs():
    """Small instances of all 8 sweep shapes (BASELINE configs 1-5 +
    the three adversarial extensions), as packed column dicts."""
    return {
        1: packed.pack(workloads.editor_replay(400)).arrays(),
        2: packed.pack(workloads.two_replica_interleaved(800)).arrays(),
        3: packed.pack(workloads.nested_tree(1500, 4)).arrays(),
        4: packed.pack(
            workloads.tombstone_heavy(600, 8)).arrays(),
        5: workloads.chain_workload(4, 2048),
        6: workloads.descending_chains(64, 2048),
        7: workloads.comb_pairs(2048),
        8: workloads.deep_paths(8, 2048),
    }


@pytest.mark.parametrize("cid", sorted(_small_configs()))
def test_full_merge_pallas_interpret_bit_identity(cid):
    """merge with use_pallas=True (interpreted Mosaic: mono_gather AND
    the fused_resolve plane sweep) == the lax path, every NodeTable
    field, production exhaustive mode."""
    arrs = _small_configs()[cid]
    t_lax = view.to_host(merge.materialize(arrs, use_pallas=False,
                                           hints="exhaustive"))
    t_pal = view.to_host(merge.materialize(arrs, use_pallas=True,
                                           hints="exhaustive",
                                           interpret=True))
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(t_pal, f)), np.asarray(getattr(t_lax, f)),
            err_msg=f"config {cid} field {f}")


def test_fallback_path_order_exact_config5():
    """ISSUE 3 acceptance: the config-5 closed-form order must hold on
    the lax path (the lax twins of the pallas superops)."""
    n = 65_536
    arrs = workloads.chain_workload(64, n)
    t = view.to_host(merge.materialize(arrs, use_pallas=False,
                                       hints="exhaustive"))
    exp = workloads.chain_expected_ts(64, n)
    seq = np.asarray(t.ts)[np.asarray(t.visible_order)]
    seq = seq[:int(t.num_visible)]
    assert int(t.num_visible) == n
    np.testing.assert_array_equal(seq, exp)


def test_full_merge_pallas_interpret_auto_mode():
    """The verified auto mode rides the same pallas plane sweep."""
    arrs = _small_configs()[5]
    t_lax = view.to_host(merge.materialize(arrs, use_pallas=False))
    t_pal = view.to_host(merge.materialize(arrs, use_pallas=True,
                                           interpret=True))
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(t_pal, f)), np.asarray(getattr(t_lax, f)),
            err_msg=f"auto field {f}")
