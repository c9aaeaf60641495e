"""The pallas bounded-span monotone gather must equal the lax reference
(ops/mono_gather.py).  Runs the Mosaic kernel in interpreter mode on CPU;
the real-TPU path is exercised by the bench."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from crdt_graph_tpu.ops import mono_gather


def _case(rng, t, r, v=5):
    # nondecreasing rid with increments in {0, 1}, like a run-id cumsum
    inc = rng.integers(0, 2, t).astype(np.int32)
    inc[0] = 0
    rid = np.cumsum(inc).astype(np.int32)
    r_eff = max(r, int(rid[-1]) + 1)
    values = rng.integers(0, min(2**23, 10 * r_eff), (v, r_eff),
                          dtype=np.int32)
    return jnp.asarray(values), jnp.asarray(rid)


@pytest.mark.parametrize("t", [7, 512, 513, 2048, 5000])
def test_interpret_matches_lax(t):
    rng = np.random.default_rng(t)
    values, rid = _case(rng, t, 64)
    want = np.asarray(mono_gather._lax_gather(values, rid))
    got = np.asarray(mono_gather.monotone_gather(values, rid,
                                                 interpret=True))
    np.testing.assert_array_equal(got, want)


def test_constant_rid():
    values = jnp.arange(40, dtype=jnp.int32).reshape(5, 8)
    rid = jnp.zeros(700, jnp.int32)
    got = np.asarray(mono_gather.monotone_gather(values, rid,
                                                 interpret=True))
    want = np.asarray(values[:, rid])
    np.testing.assert_array_equal(got, want)


def test_full_merge_with_pallas_rank_interpret():
    """The whole merge kernel with the pallas rank path (interpreted)
    must match the default lax path on a real log."""
    from crdt_graph_tpu.codec import packed
    from crdt_graph_tpu.ops import merge, view
    from test_merge_kernel import _random_session

    _, ops = _random_session(77, n_replicas=3, steps=70)
    p = packed.pack(ops)
    t_lax = view.to_host(merge.materialize(p.arrays()))
    t_pal = view.to_host(merge.materialize(p.arrays(), use_pallas=True,
                                           interpret=True))
    np.testing.assert_array_equal(np.asarray(t_pal.doc_index),
                                  np.asarray(t_lax.doc_index))
    np.testing.assert_array_equal(np.asarray(t_pal.visible_order),
                                  np.asarray(t_lax.visible_order))
    assert view.visible_values(t_pal, p.values) == \
        view.visible_values(t_lax, p.values)


def test_auto_falls_back_on_cpu():
    """On a CPU backend the auto mode must pick the lax path (and agree)."""
    rng = np.random.default_rng(0)
    values, rid = _case(rng, 300, 16)
    got = np.asarray(mono_gather.monotone_gather(values, rid))
    np.testing.assert_array_equal(got, np.asarray(values[:, rid]))
