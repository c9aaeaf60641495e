"""The engine's two apply paths — host mirror (small deltas) and batched
kernel (large deltas) — must be indistinguishable: same tree, same log,
same atomicity, same view semantics, all pinned against the oracle.
"""
import numpy as np
import pytest

import crdt_graph_tpu as crdt
from crdt_graph_tpu import engine
from crdt_graph_tpu.core import operation as op_mod
from crdt_graph_tpu.host_tree import HostTree

from test_merge_kernel import _random_session


def snapshot(e):
    return (e.visible_values(), e.visible_paths(), e.log_length,
            len(e), e.timestamp, e.cursor)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_host_vs_kernel_vs_oracle(seed):
    """One big-batch apply (kernel), per-op applies (host), and the oracle
    all converge to the same tree on a >threshold random session."""
    merged, ops = _random_session(seed, n_replicas=4, steps=400)
    assert len(ops) > engine.DELTA_THRESHOLD, "session too small to force"

    big = engine.init(42)
    big.apply(crdt.Batch(tuple(ops)))           # kernel path

    small = engine.init(42)
    for op in ops:
        small.apply(op)                          # host path, op by op

    oracle_vis = merged.visible_values()
    assert big.visible_values() == oracle_vis
    assert small.visible_values() == oracle_vis
    assert big.visible_paths() == small.visible_paths()
    assert big.log_length == small.log_length == len(ops)


def test_threshold_boundary_equal():
    """Batches of exactly DELTA_THRESHOLD and DELTA_THRESHOLD+1 leaves land
    on different paths but must produce identical state."""
    rid = 3
    for count in (engine.DELTA_THRESHOLD, engine.DELTA_THRESHOLD + 1):
        ops, prev = [], 0
        for i in range(1, count + 1):
            ts = rid * 2**32 + i
            ops.append(crdt.Add(ts, (prev,), i))
            prev = ts
        e = engine.init(1)
        e.apply(crdt.Batch(tuple(ops)))
        assert e.visible_values() == list(range(1, count + 1))
        assert e.log_length == count


@pytest.mark.parametrize("big", [False, True])
def test_failing_batch_leaves_replica_untouched(big):
    """Atomicity on BOTH paths: a NotFound mid-batch raises and rolls back
    everything (host: undo journal; kernel: materialise-then-commit)."""
    e = engine.init(1)
    e.add("a").add("b")
    before = snapshot(e)
    rid = 7
    count = engine.DELTA_THRESHOLD + 1 if big else 10
    ops, prev = [], 0
    for i in range(1, count + 1):
        ts = rid * 2**32 + i
        ops.append(crdt.Add(ts, (prev,), i))
        prev = ts
    # poison an op mid-batch: anchored at a timestamp nobody has
    ops[count // 2] = crdt.Add(rid * 2**32 + count + 5, (999999,), "x")
    with pytest.raises(crdt.OperationFailedError):
        e.apply(crdt.Batch(tuple(ops)))
    assert snapshot(e) == before


def test_interleaved_host_and_kernel_applies():
    """Alternating small and large applies stays oracle-exact."""
    merged, ops = _random_session(21, n_replicas=3, steps=200)
    e = engine.init(42)
    o = crdt.init(42)
    i = 0
    chunk_sizes = [1, 3, engine.DELTA_THRESHOLD + 1, 2, 50]
    k = 0
    while i < len(ops):
        n = chunk_sizes[k % len(chunk_sizes)]
        k += 1
        chunk = crdt.Batch(tuple(ops[i:i + n]))
        e.apply(chunk)
        o = o.apply(chunk)
        i += n
    assert e.visible_values() == o.visible_values()
    assert e.log_length == len(op_mod.to_list(o.operations_since(0)))


def test_absorbed_duplicates_on_host_path():
    """Redelivering a whole delta through the host path is absorbed: log
    stable, no error, last_operation empty-ish (CRDTree.elm:318-319)."""
    e = engine.init(1)
    e.add("a").add("b")
    delta = e.operations_since(0)
    n0 = e.log_length
    e.apply(delta)
    assert e.log_length == n0
    assert list(op_mod.to_list(e.last_operation)) == []


def test_mirror_rebuild_after_kernel_matches_replay():
    """HostTree.from_table (vectorised rebuild) must equal a sequential
    replay of the same log — links, paths, visibility, everything."""
    merged, ops = _random_session(31, n_replicas=4, steps=400)
    e = engine.init(9)
    e.apply(crdt.Batch(tuple(ops)))             # kernel path
    rebuilt = e._ensure_mirror()                # from_table
    replayed = HostTree(e._max_depth)
    for op in ops:
        if isinstance(op, crdt.Add):
            replayed.apply_add(op.ts, tuple(op.path), op.value)
        else:
            replayed.apply_delete(tuple(op.path))
    a = [(rebuilt.path_of(s), rebuilt.values[int(rebuilt.value_ref[s])])
         for s in rebuilt.iter_visible()]
    b = [(replayed.path_of(s), replayed.values[int(replayed.value_ref[s])])
         for s in replayed.iter_visible()]
    assert a == b


def test_local_batch_rollback_on_host_path():
    """A failing local batch() rolls the mirror back in place; outstanding
    views stay valid (no slot reassignment happened)."""
    e = engine.init(1)
    e.add("a").add("b")
    n = e.get(e.visible_paths()[0])
    before = snapshot(e)

    def boom(t):
        raise RuntimeError("nope")

    with pytest.raises(RuntimeError):
        e.batch([lambda t: t.add("c"), lambda t: t.add("d"), boom])
    assert snapshot(e) == before
    assert n.value == "a"                       # view survived the rollback


# -- the visible sequence of a kernel-merged document, read from its table --

def _chain(rid, n):
    """One replica's causal chain of ``n`` adds from the head."""
    ops, prev = [], 0
    for c in range(1, n + 1):
        ts = rid * 2**32 + c
        ops.append(crdt.Add(ts, (prev,), f"c{rid}.{c}"))
        prev = ts
    return ops


def _nested8_ops_with_deletes():
    """A depth-8 branch skeleton with chains under every level from four
    replicas, then deletes: the depth-5 branch (its subtrees go dead) and
    every 7th chain node outside it (tombstones; a delete inside a dead
    branch would be absorbed)."""
    ops, paths = [], []
    skeleton, path = [()], ()
    for c in range(1, 8):
        ts = 2**32 + c
        ops.append(crdt.Add(ts, path + (0,), f"b{c}"))
        path = path + (ts,)
        skeleton.append(path)
    for r in range(1, 5):
        for level, parent in enumerate(skeleton):
            anchor = parent + (0,)
            for c in range(1, 41):
                ts = r * 2**32 + 1000 + level * 100 + c
                ops.append(crdt.Add(ts, anchor, f"x{r}.{level}.{c}"))
                anchor = parent + (ts,)
                if level < 5:
                    paths.append(anchor)
    ops.append(crdt.Delete(skeleton[5]))
    ops.extend(crdt.Delete(p) for p in paths[::7])
    return ops


def _kernel_doc(case):
    """(tree, oracle) after one kernel-size ``apply_packed`` per case;
    ``partial_dups`` re-delivers an applied prefix, so the commit absorbs
    duplicates and takes ``_invalidate`` (no table parked)."""
    from crdt_graph_tpu.codec import packed as packed_mod
    ops = (_nested8_ops_with_deletes() if case == "nested8_deletes"
           else _chain(1, 500) + _chain(2, 500) + _chain(3, 500))
    assert len(ops) >= 4 * engine.DELTA_THRESHOLD
    e = engine.init(42)
    if case == "partial_dups":
        e.apply_packed(packed_mod.pack(ops[:1100],
                                       max_depth=e._max_depth))
    e.apply_packed(packed_mod.pack(ops, max_depth=e._max_depth))
    assert e.log_length == len(ops)
    assert (e._table is None) == (case == "partial_dups")
    return e, crdt.init(42).apply(crdt.Batch(tuple(ops)))


CASES = ["chain", "nested8_deletes", "partial_dups"]


@pytest.mark.parametrize("case", CASES)
def test_visible_from_table_matches_mirror_and_oracle(case):
    """After a kernel apply the visible sequence comes from the merged
    table without building the mirror, and equals both the oracle's and
    the mirror's walk: tombstones and dead branches included."""
    e, oracle = _kernel_doc(case)
    c0 = engine.view_counts()
    got = e.visible_values()
    c1 = engine.view_counts()
    assert e._mirror is None
    assert c1["visible_from_table"] - c0["visible_from_table"] == 1
    assert c1["mirror_builds"] == c0["mirror_builds"]
    assert got == oracle.visible_values()
    e._ensure_mirror()
    assert engine.view_counts()["mirror_builds"] == c1["mirror_builds"] + 1
    assert e.visible_values() == got            # the mirror's walk


@pytest.mark.parametrize("case", CASES)
def test_first_host_delta_after_kernel_commit_builds_mirror(case):
    """The first host-path delta after a kernel commit builds the mirror
    from the table, once, and stays oracle-exact; later reads walk it."""
    e, oracle = _kernel_doc(case)
    e.visible_values()
    c0 = engine.view_counts()
    delta = crdt.Batch(tuple(_chain(9, 3)))
    e.apply(delta)
    oracle = oracle.apply(delta)
    assert e._mirror is not None
    assert e.visible_values() == oracle.visible_values()
    c1 = engine.view_counts()
    assert c1["mirror_builds"] - c0["mirror_builds"] == 1
    assert c1["visible_from_table"] == c0["visible_from_table"]


@pytest.mark.parametrize("case", CASES)
def test_matz_arrays_from_table_equal_the_mirrors(case):
    """A kernel-merged document's materialization artifact comes straight
    from its table, with no mirror built, and holds exactly what the
    mirror built from that table would export."""
    e, _ = _kernel_doc(case)
    e.visible_values()                          # the publish
    c0 = engine.view_counts()["mirror_builds"]
    got = e._matz_export()
    assert e._mirror is None
    assert engine.view_counts()["mirror_builds"] == c0
    m = e._ensure_mirror()
    want = m.export_arrays()
    assert sorted(got["arrs"]) == sorted(want)
    for name, col in want.items():
        assert got["arrs"][name].dtype == col.dtype, name
        np.testing.assert_array_equal(got["arrs"][name], col, err_msg=name)
    assert (got["n"], got["nvis"]) == (m.n, m.nvis)
    assert list(got["values"]) == m.values


def test_next_kernel_merge_moves_published_tables_to_host():
    """A visible read leaves a device table parked, its host copy in
    flight; the next kernel merge in the process, on any document, swaps
    the copy in before it launches, and never over a table that a later
    edit replaced."""
    a, oracle_a = _kernel_doc("chain")
    b, oracle_b = _kernel_doc("nested8_deletes")
    want_a = a.visible_values()
    want_b = b.visible_values()
    assert not isinstance(a._table.status, np.ndarray)   # on the device
    assert not isinstance(b._table.status, np.ndarray)
    delta = crdt.Batch(tuple(_chain(9, 3)))
    b.apply(delta)                              # host path: table dropped
    oracle_b = oracle_b.apply(delta)
    assert b._table is None
    _kernel_doc("chain")                        # another document's merge
    assert isinstance(a._table.status, np.ndarray)
    assert b._table is None
    assert a._mirror is None
    assert a.visible_values() == want_a == oracle_a.visible_values()
    assert b.visible_values() == oracle_b.visible_values() != want_b
