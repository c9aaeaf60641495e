"""Sharded-merge parity: the multi-chip paths must produce bit-identical
tables to the single-device kernel, on the simulated 8-device CPU mesh
(conftest.py sets XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
import os
import random
import subprocess
import sys

import numpy as np
import pytest

import jax

import crdt_graph_tpu as crdt
from crdt_graph_tpu.codec import packed
from crdt_graph_tpu.ops import merge, view
from crdt_graph_tpu.parallel import mesh as mesh_mod
from crdt_graph_tpu.parallel import opsaxis

from test_merge_kernel import _random_session


@pytest.fixture(scope="module")
def session_ops():
    merged, ops = _random_session(21, n_replicas=4, steps=150)
    return merged.visible_values(), ops


def test_eight_way_ops_sharding(session_ops):
    want, ops = session_ops
    p = packed.pack(ops)
    t = view.to_host(opsaxis.materialize(p.arrays(), k=8))
    ref = view.to_host(merge.materialize(p.arrays()))
    assert view.visible_values(t, p.values) == want
    for field in ("ts", "parent", "doc_index", "order", "visible_order",
                  "status"):
        np.testing.assert_array_equal(getattr(t, field), getattr(ref, field))


def test_batched_docs_sharding(session_ops):
    _, ops = session_ops
    rng = random.Random(3)
    docs = []
    wants = []
    for d in range(8):
        perm = ops[:]
        rng.shuffle(perm)
        sub = perm[: 40 + 10 * d]
        docs.append(packed.pack(sub, capacity=256))
        t = view.to_host(merge.materialize(docs[-1].arrays()))
        wants.append(view.visible_values(t, docs[-1].values))
    stacked = mesh_mod.stack_packed(docs)
    m = mesh_mod.make_mesh(n_docs=8, n_ops=1)
    tb = view.to_host(mesh_mod.batched_materialize(stacked, m))
    for d in range(8):
        row = jax.tree.map(lambda a: a[d], tb)
        assert view.visible_values(row, docs[d].values) == wants[d]


def test_batched_exhaustive_hints_parity(session_ops):
    """The opt-in cond-free hinted batched kernel must match the safe
    (join) batched kernel bit for bit on pack-produced batches."""
    _, ops = session_ops
    docs = [packed.pack(ops[: 60 + 20 * d], capacity=256) for d in range(8)]
    stacked = mesh_mod.stack_packed(docs)
    m = mesh_mod.make_mesh(n_docs=8, n_ops=1)
    safe = view.to_host(mesh_mod.batched_materialize(stacked, m))
    fast = view.to_host(
        mesh_mod.batched_materialize(stacked, m, exhaustive_hints=True))
    for field in ("ts", "doc_index", "visible_order", "status"):
        np.testing.assert_array_equal(getattr(fast, field),
                                      getattr(safe, field), field)


def test_2d_mesh_docs_by_ops(session_ops):
    want, ops = session_ops
    p = packed.pack(ops)
    docs = [p, p, p, p]
    stacked = mesh_mod.stack_packed(docs)
    m = mesh_mod.make_mesh(n_docs=4, n_ops=2)
    tb = view.to_host(
        mesh_mod.batched_materialize(stacked, m, shard_ops_axis=True))
    for d in range(4):
        row = jax.tree.map(lambda a: a[d], tb)
        assert view.visible_values(row, p.values) == want


def test_uneven_doc_axis_rejected():
    p = packed.pack([crdt.Add(1, (0,), "a")])
    stacked = mesh_mod.stack_packed([p, p, p])
    m = mesh_mod.make_mesh(n_docs=8, n_ops=1)
    with pytest.raises(ValueError):
        mesh_mod.batched_materialize(stacked, m)


def test_graft_entry_dryrun_multichip_8():
    """``__graft_entry__.dryrun_multichip(8)`` end to end in a fresh
    process (it pins its own 8-device CPU platform): the docs × ops
    batched merge and one document over the 8-wide ops axis, each
    equal to the single-device kernel in every table field."""
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8); "
         "print('dryrun ok')"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "dryrun ok" in out.stdout
