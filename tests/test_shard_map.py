"""Explicitly partitioned merge (parallel/shard.py) vs the whole-array
kernel: bit-identical tables on large mixed batches, adversarial shapes,
and hostile hints, on the simulated 8-device CPU mesh (VERDICT r3
missing-2 "done" criteria)."""
import os

import numpy as np
import pytest

import jax

import crdt_graph_tpu as crdt
from crdt_graph_tpu.bench import workloads
from crdt_graph_tpu.codec import packed
from crdt_graph_tpu.ops import merge, view
from crdt_graph_tpu.parallel import mesh as mesh_mod
from crdt_graph_tpu.parallel import shard

# the 256k bit-identity suite runs with the packed multi-column layout
# pinned ON (the round-6 default; a hard set so neither an exported
# B-leg override nor a future default change can silently weaken what
# this file proves)
os.environ["GRAFT_PACK_GATHER"] = "1"

FIELDS = ("ts", "parent", "depth", "value_ref", "paths", "exists",
          "tombstone", "dead", "visible", "doc_index", "order",
          "visible_order", "num_nodes", "num_visible", "status")


@pytest.fixture(scope="module")
def ops_mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    return mesh_mod.make_mesh(n_docs=1, n_ops=8)


def assert_identical(arrs, mesh, hints="auto"):
    """Pad once, run both paths on the SAME padded arrays, compare every
    table field bitwise."""
    n = mesh_mod.round_up(arrs["kind"].shape[0], mesh.shape["ops"])
    padded = mesh_mod._pad_ops_to(arrs, n)
    want = merge.materialize(padded)
    got = shard.shard_materialize(padded, mesh, hints=hints)
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(want, f)), np.asarray(getattr(got, f)), f)
    return got


def test_large_mixed_batch_identity(ops_mesh):
    """≥256k ops with deletes through the explicit schedule — the r3
    verdict's 'done' bar for genuinely partitioned merges."""
    arrs = workloads.chain_with_deletes(229_376, 8)
    assert arrs["kind"].shape[0] >= 256_000
    assert (arrs["kind"] == packed.KIND_DELETE).sum() > 10_000
    assert_identical(arrs, ops_mesh)


def test_adversarial_shapes_identity(ops_mesh):
    """The bench's adversarial generators (descending chains, comb
    pairs, deep paths) at 16k ops: worst-case sibling contention and
    fragmentation through the partitioned resolve.  (Shrunk from 64k —
    the generators' adversarial structure is size-independent and the
    ≥256k scale bar lives in test_large_mixed_batch_identity; ISSUE 12
    tier-1 budget.)"""
    for arrs in (workloads.chain_workload(64, 16_384),
                 workloads.descending_chains(256, 16_384),
                 workloads.comb_pairs(16_384),
                 workloads.deep_paths(64, 16_384, max_depth=16)):
        assert_identical(arrs, ops_mesh)


def test_chain_closed_form_through_shard_map(ops_mesh):
    """Not just self-consistency: the partitioned result matches the
    closed-form expected visible sequence for the 64-chain interleave."""
    arrs = workloads.chain_workload(64, 65_536)
    got = assert_identical(arrs, ops_mesh)
    want_seq = workloads.chain_expected_ts(64, 65_536)
    seq = np.asarray(got.ts)[np.asarray(got.visible_order)][
        :int(got.num_visible)]
    np.testing.assert_array_equal(seq, want_seq)


def test_exhaustive_mode_identity(ops_mesh):
    """Vouched (pack-produced) hints through the cond-free mode."""
    from test_merge_kernel import _random_session
    _, ops = _random_session(97, n_replicas=4, steps=400)
    p = packed.pack(ops)
    assert p.hints_vouched
    assert_identical(p.arrays(), ops_mesh, hints="exhaustive")


def test_hostile_hints_fall_back_identically(ops_mesh):
    """Corrupted ranks/links trip the distributed verification; the
    gathered batch takes the shared sorted+join fallback and the result
    still matches the stock kernel byte for byte."""
    from test_merge_kernel import _random_session
    _, ops = _random_session(98, n_replicas=3, steps=300)
    p = packed.pack(ops)
    arrs = dict(p.arrays())
    rng = np.random.default_rng(3)
    r = arrs["ts_rank"].copy()
    adds = np.nonzero(r >= 0)[0]
    r[adds] = rng.permutation(r[adds])
    arrs["ts_rank"] = r
    bad = arrs["anchor_pos"].copy()
    bad[bad >= 0] = 0
    arrs["anchor_pos"] = bad
    assert_identical(arrs, ops_mesh)


def test_missing_hint_columns_rejected(ops_mesh):
    arrs = {k: v for k, v in
            packed.pack([crdt.Add(1, (0,), "a")]).arrays().items()
            if k != "ts_rank"}
    with pytest.raises(ValueError, match="hint columns"):
        shard.shard_materialize(arrs, ops_mesh)


def test_collective_volume_explicit_vs_auto(ops_mesh):
    """The measurable claim behind the module: the explicit schedule's
    collective traffic is accounted from compiled HLO and compared with
    XLA's auto-partitioning of the whole-array kernel on the same
    sharded inputs (VERDICT r3 asked for exactly this comparison)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    arrs = workloads.chain_workload(64, 65_536)
    mesh = ops_mesh
    padded = mesh_mod._pad_ops_to(
        arrs, mesh_mod.round_up(arrs["kind"].shape[0], 8))
    with jax.enable_x64(True):
        dev = {k: jax.device_put(
            v, NamedSharding(mesh, P("ops") if v.ndim == 1
                             else P("ops", None)))
            for k, v in padded.items()}

        explicit = shard.collective_stats(
            shard._shard_materialize_jit
            .lower(dev, mesh, "auto", False, True).compile().as_text())

        auto = shard.collective_stats(
            jax.jit(lambda o: merge._materialize.__wrapped__(
                o, False, None, True))
            .lower(dev).compile().as_text())

    print(f"\ncollectives explicit={explicit}\ncollectives auto={auto}")
    # both paths genuinely communicate, and the explicit schedule's
    # traffic must stay within the same order as auto-partitioning
    assert explicit["count"] > 0 and explicit["total_bytes"] > 0
    assert auto["count"] > 0
    assert explicit["total_bytes"] <= 2 * max(auto["total_bytes"], 1)
