"""Merge-kernel parity suite: the jitted semilattice join must reproduce the
sequential oracle on every fixture and on randomized causally-valid
multi-replica logs, under arbitrary permutations of delivery order.

This is the convergence/race-detection strategy of the framework (SURVEY §5):
random op permutations and partitions must produce identical visible
sequences, with the pure oracle as the correctness reference.
"""
import random

import jax
import numpy as np
import pytest

import crdt_graph_tpu as crdt
from crdt_graph_tpu import Add, Batch, Delete
from crdt_graph_tpu.codec import packed
from crdt_graph_tpu.core import operation as op_mod
from crdt_graph_tpu.ops import merge, view

OFFSET = 2**32


def kernel_visible(ops, max_depth=16):
    p = packed.pack(ops, max_depth=max_depth)
    t = view.to_host(merge.materialize(p.arrays()))
    return view.visible_values(t, p.values), t, p


def oracle_visible(ops):
    tree = crdt.init(99)
    for op in ops:
        try:
            tree = tree.apply(op)
        except crdt.CRDTError:
            pass
    return tree.visible_values(), tree


# -- the canonical convergence fixtures (tests/NodeTest.elm:23-60) --------

@pytest.mark.parametrize("order", [(6, 5, 4), (4, 6, 5), (4, 5, 6),
                                   (5, 4, 6), (5, 6, 4), (6, 4, 5)])
def test_interleaving_converges(order):
    ops = [Add(1, (0,), 1), Add(2, (1,), 2), Add(3, (2,), 3)] + \
        [Add(t, (1,), t) for t in order]
    vis, _, _ = kernel_visible(ops)
    assert vis == [1, 6, 5, 4, 2, 3]


def test_append_order_converges():
    for ops in ([Add(1, (0,), "a"), Add(2, (0,), "b")],
                [Add(2, (0,), "b"), Add(1, (0,), "a")]):
        vis, _, _ = kernel_visible(ops)
        assert vis == ["b", "a"]


# -- reference state-machine scenarios through the kernel -----------------

def test_insert_between():
    ops = [Add(1, (0,), "a"), Add(2, (1,), "b"), Add(3, (2,), "c"),
           Add(4, (1,), "z")]
    vis, _, _ = kernel_visible(ops)
    assert vis == ["a", "z", "b", "c"]


def test_delete_kills_subtree():
    ops = [Add(1, (0,), "a"), Add(2, (1, 0), "b"), Add(3, (1,), "c"),
           Delete((1,))]
    vis, t, p = kernel_visible(ops)
    assert vis == ["c"]
    assert view.statuses(t, p.num_ops) == ["applied"] * 4


def test_add_to_deleted_branch_absorbed():
    ops = [Add(1, (0,), "a"), Delete((1,)), Add(2, (1, 0), "b")]
    vis, t, p = kernel_visible(ops)
    assert vis == []
    assert view.statuses(t, p.num_ops) == \
        ["applied", "applied", "already_applied"]


def test_add_idempotent():
    ops = [Add(1, (0,), "a")] * 4
    vis, t, p = kernel_visible(ops)
    assert vis == ["a"]
    assert view.statuses(t, p.num_ops) == \
        ["applied"] + ["already_applied"] * 3


def test_delete_idempotent():
    ops = [Add(1, (0,), "a")] + [Delete((1,))] * 5
    vis, t, p = kernel_visible(ops)
    assert vis == []
    assert view.statuses(t, p.num_ops) == \
        ["applied", "applied"] + ["already_applied"] * 4


def test_empty_path_ops_flagged():
    ops = [crdt.Add(1, (0,), "a"), Add(10, (), "y"), Delete(())]
    vis, t, p = kernel_visible(ops)
    assert vis == ["a"]
    assert view.statuses(t, p.num_ops) == \
        ["applied", "invalid_path", "invalid_path"]


def test_delete_sentinel_already_applied():
    # deleting a branch head (path ending in 0) finds the sentinel tombstone
    ops = [crdt.Add(1, (0,), "a"), Delete((0,)), Delete((1, 0))]
    vis, t, p = kernel_visible(ops)
    assert vis == ["a"]
    assert view.statuses(t, p.num_ops) == \
        ["applied", "already_applied", "already_applied"]


def test_missing_anchor_flagged():
    ops = [Add(1, (0,), "a"), Add(2, (9,), "b")]
    vis, t, p = kernel_visible(ops)
    assert vis == ["a"]
    assert view.statuses(t, p.num_ops) == ["applied", "not_found"]


def test_missing_intermediate_flagged():
    ops = [Add(1, (0,), "a"), Add(2, (7, 0), "b")]
    vis, t, p = kernel_visible(ops)
    assert view.statuses(t, p.num_ops) == ["applied", "invalid_path"]


def test_invalid_parent_cascades():
    # b's parent add is invalid, so b and everything under it is invalid too
    ops = [Add(1, (0,), "a"), Add(2, (9, 0), "b"), Add(3, (2, 0), "c")]
    vis, t, p = kernel_visible(ops)
    assert vis == ["a"]
    st = view.statuses(t, p.num_ops)
    assert st[1] == "invalid_path" and st[2] == "invalid_path"


def test_nested_branches():
    ops = [Add(1, (0,), "a"), Add(2, (1, 0), "b"), Add(3, (1, 2, 0), "c"),
           Add(4, (1, 2, 3, 0), "d"), Add(5, (1, 2, 3, 4, 0), "e"),
           Add(6, (1, 2, 3, 4, 5), "f")]
    vis, t, p = kernel_visible(ops)
    assert vis == ["a", "b", "c", "d", "e", "f"]
    assert view.get_value(t, p.values, [1, 2, 3]) == "c"
    assert view.get_value(t, p.values, [1, 2, 3, 4, 6]) == "f"
    assert view.get_value(t, p.values, [9]) is None


def test_tombstone_anchor_still_orders():
    # chain a(10) b(30)† then insert 20 after 10: must skip past the
    # tombstone (divergence note in core/node.py applies to both engines)
    ops = [Add(10, (0,), "a"), Add(30, (10,), "b"), Delete((30,)),
           Add(20, (10,), "c")]
    vis, _, _ = kernel_visible(ops)
    assert vis == ["a", "c"]


@pytest.mark.parametrize("cycle", [
    # 1-cycle: an op anchored at its own timestamp
    [Add(5, (5,), "a")],
    # 2-cycle: each op anchors at the other
    [Add(5, (7,), "a"), Add(7, (5,), "b")],
    # 3-cycle
    [Add(5, (9,), "a"), Add(7, (5,), "b"), Add(9, (7,), "c")],
])
def test_anchor_cycles_rejected_like_the_oracle(cycle):
    """An adversarial op set closing an anchor loop admits NO serial
    order — the oracle rejects every member (anchor absent on arrival),
    and the kernel's cycle check must agree instead of letting the loop
    corrupt the order forest.  Surrounding valid ops are unaffected."""
    ops = [Add(1, (0,), "x")] + cycle + [Add(2, (1,), "y")]
    want, _ = oracle_visible(ops)
    vis, t, p = kernel_visible(ops)
    assert want == ["x", "y"]
    assert vis == want
    st = view.statuses(t, p.num_ops)
    assert st[0] == "applied" and st[-1] == "applied"
    assert all(s in ("not_found", "invalid_path") for s in st[1:-1])


def test_long_ascending_chain_with_late_small_anchor():
    """Regression (round-3 soak): an ASCENDING anchor chain resolves each
    node's nearest-smaller-ancestor instantly (frozen answers), and a
    late smaller-timestamp op anchored at the chain tail must then walk
    that answer chain — longer than the chase's log-trip cap.  The
    binary-lifting fallback finishes the walk exactly; without it the
    node mis-parents and the visible order flips its last two entries."""
    R = 2 * 2**32
    chain_len = 40                       # > ceil(log2(M)) + 2 trips
    ops = [Add(R + 1, (0,), "A"), Add(R + 2, (R + 1,), "X")]
    prev = R + 1
    for k in range(3, chain_len + 3):
        ops.append(Add(R + k, (prev,), f"c{k}"))
        prev = R + k
    # replica 1: smaller ts than every chain node, anchored at the tail
    ops.append(Add(1 * 2**32 + 1, (prev,), "Z"))
    vis, _, _ = kernel_visible(ops)
    want, _ = oracle_visible(ops)
    assert vis == want
    # the reference order: Z drifts right past X (larger ts) to the end
    assert vis[-2:] == ["X", "Z"]


# -- permutation invariance on fixed fixtures -----------------------------

def test_permutation_invariance_small():
    base = [Add(1, (0,), "a"), Add(2, (1, 0), "b"), Add(3, (1, 2), "c"),
            Add(4, (1,), "d"), Delete((3,)), Add(5, (2**32 + 1,), "e")]
    # note: op 5 anchors at a missing node — stays invalid in every order
    want, _ = oracle_visible(base)
    rng = random.Random(7)
    for _ in range(12):
        perm = base[:]
        rng.shuffle(perm)
        vis, _, _ = kernel_visible(perm)
        assert vis == want


def test_out_of_range_replica_id_rejected_loudly():
    # timestamps at/above 2**62 collide with kernel sentinels; pack refuses
    with pytest.raises(ValueError):
        packed.pack([Add(2**62 + 1, (0,), "a")])
    with pytest.raises(ValueError):
        packed.pack([Delete((2**62 + 1,))])


# -- link hints: the hinted resolution path (ops/merge.py step 4) ---------

def test_hinted_and_joined_paths_agree():
    """The same batch through the hinted path (pack's link hints) and the
    join path (hint columns stripped) must produce identical tables."""
    merged, ops = _random_session(21, n_replicas=3, steps=80)
    p = packed.pack(ops)
    arrs = p.arrays()
    t_hint = view.to_host(merge.materialize(arrs))
    stripped = {k: v for k, v in arrs.items()
                if k not in ("parent_pos", "anchor_pos", "target_pos")}
    t_join = view.to_host(merge.materialize(stripped))
    assert view.visible_values(t_hint, p.values) == \
        view.visible_values(t_join, p.values)
    assert view.statuses(t_hint, p.num_ops) == \
        view.statuses(t_join, p.num_ops)
    assert np.array_equal(np.asarray(t_hint.doc_index),
                          np.asarray(t_join.doc_index))


def test_mislinked_hints_fall_back_to_join():
    """Corrupted hints (every hint pointing at op 0) must not change the
    result — the kernel verifies on device and falls back to the join."""
    merged, ops = _random_session(22, n_replicas=3, steps=60)
    want = merged.visible_values()
    p = packed.pack(ops)
    arrs = dict(p.arrays())
    for k in ("parent_pos", "anchor_pos", "target_pos"):
        bad = np.asarray(arrs[k]).copy()
        bad[bad >= 0] = 0           # mislink everything resolvable
        arrs[k] = bad
    t = view.to_host(merge.materialize(arrs))
    assert view.visible_values(t, p.values) == want


def test_hint_modes_agree():
    """auto (cond fallback), exhaustive (no join compiled), and join
    (hints ignored) must produce identical tables on pack-produced
    batches — including one with a genuinely missing anchor (unresolved
    ref: auto takes the join at runtime, exhaustive resolves to
    not-found directly; same answer)."""
    merged, ops = _random_session(33, n_replicas=3, steps=70)
    ops = ops + [Add(77 * 2**32 + 1, (12345,), "orphan")]  # absent anchor
    p = packed.pack(ops)
    arrs = p.arrays()
    tables = [view.to_host(merge.materialize(arrs, hints=h))
              for h in (None, "exhaustive", "join")]
    for t in tables[1:]:
        assert view.visible_values(t, p.values) == \
            view.visible_values(tables[0], p.values)
        assert view.statuses(t, p.num_ops) == \
            view.statuses(tables[0], p.num_ops)
        assert np.array_equal(np.asarray(t.doc_index),
                              np.asarray(tables[0].doc_index))


def test_hostile_ranks_fall_back():
    """Corrupting ts_rank in every distinct way (shuffle, collision, gap,
    missing, out-of-range) must trip the device-side rank verification
    and route the batch down the sorted+join branch — identical tables,
    wrong hints cost speed never correctness (ops/merge.py steps 1-4)."""
    merged, ops = _random_session(91, n_replicas=3, steps=60)
    p = packed.pack(ops)
    base = p.arrays()
    want_t = view.to_host(merge.materialize(base, hints="join"))
    want_vals = view.visible_values(want_t, p.values)
    want_status = view.statuses(want_t, p.num_ops)
    rng = np.random.default_rng(5)
    adds = np.nonzero(base["ts_rank"] >= 0)[0]

    def corrupt(name, mutate):
        arrs = dict(base)
        r = base["ts_rank"].copy()
        mutate(r)
        arrs["ts_rank"] = r
        t = view.to_host(merge.materialize(arrs))     # auto mode
        assert view.visible_values(t, p.values) == want_vals, name
        assert view.statuses(t, p.num_ops) == want_status, name
        assert np.array_equal(np.asarray(t.doc_index),
                              np.asarray(want_t.doc_index)), name

    corrupt("shuffled",
            lambda r: r.__setitem__(adds, rng.permutation(r[adds])))
    corrupt("collision", lambda r: r.__setitem__(adds[1], r[adds[0]]))
    corrupt("gap", lambda r: r.__setitem__(adds, r[adds] + 1))
    corrupt("missing", lambda r: r.__setitem__(adds[2], -1))
    corrupt("oob", lambda r: r.__setitem__(adds[0], 10**6))
    corrupt("all_missing", lambda r: r.fill(-1))


def test_concat_reresolves_cross_hints():
    """concat must re-resolve each side's unresolved refs against the
    other side so the union's hints stay exhaustive (b's ops anchored in
    a, and a's shuffled ops anchored in b)."""
    base = [Add(1, (0,), "a"), Add(2, (1,), "b")]
    delta = [Add(3, (2,), "c"), Delete((1,))]    # refs into base
    u = packed.concat(packed.pack(base), packed.pack(delta))
    assert int(u.anchor_pos[2]) == 1             # c's anchor = b (pos 1)
    assert int(u.target_pos[3]) == 0             # delete target = a
    t = view.to_host(merge.materialize(u.arrays()))
    assert view.visible_values(t, u.values) == ["b", "c"]
    # reverse direction: first part references ops living in the second
    back = packed.concat(packed.pack(delta), packed.pack(base))
    assert int(back.anchor_pos[0]) == 3          # c's anchor = b at pos 3
    assert int(back.target_pos[1]) == 2          # delete target = a at pos 2
    t2 = view.to_host(merge.materialize(back.arrays()))
    assert view.visible_values(t2, back.values) == ["b", "c"]


# -- randomized causal multi-replica logs vs the oracle -------------------

def _random_session(seed, n_replicas=4, steps=120):
    """Simulate replicas editing + syncing through the oracle API; return
    (fully merged oracle tree, full op list)."""
    rng = random.Random(seed)
    trees = [crdt.init(r + 1) for r in range(n_replicas)]
    for _ in range(steps):
        i = rng.randrange(n_replicas)
        t = trees[i]
        roll = rng.random()
        try:
            if roll < 0.5:
                t = t.add(rng.randrange(1000))
            elif roll < 0.65:
                t = t.add_branch(rng.randrange(1000))
            elif roll < 0.8:
                # delete a random visible node
                vis = []
                t.walk(lambda n, acc: (crdt.TAKE, acc.append(n.path) or acc),
                       vis)
                if vis:
                    t = t.delete(rng.choice(vis))
            else:
                # sync: pull everything from a random peer
                j = rng.randrange(n_replicas)
                if j != i:
                    t = t.apply(trees[j].operations_since(0))
        except crdt.CRDTError:
            pass
        trees[i] = t
    # full mesh sync to convergence
    for i in range(n_replicas):
        for j in range(n_replicas):
            if i != j:
                trees[i] = trees[i].apply(trees[j].operations_since(0))
    merged = trees[0]
    ops = op_mod.to_list(merged.operations_since(0))
    return merged, ops


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_random_session_parity(seed):
    merged, ops = _random_session(seed)
    want = merged.visible_values()
    vis, _, _ = kernel_visible(ops)
    assert vis == want
    # convergence under random permutation of the op log
    rng = random.Random(seed + 100)
    perm = ops[:]
    rng.shuffle(perm)
    vis_p, _, _ = kernel_visible(perm)
    assert vis_p == want


@pytest.mark.parametrize("seed", [5, 6])
def test_random_session_partition_merge(seed):
    """Splitting a log in two and concatenating the packed halves (the
    semilattice union) must equal materialising the whole."""
    merged, ops = _random_session(seed, n_replicas=3, steps=80)
    want = merged.visible_values()
    k = len(ops) // 3
    a, b = packed.pack(ops[:k]), packed.pack(ops[k:])
    u = packed.concat(a, b)
    t = view.to_host(merge.materialize(u.arrays()))
    assert view.visible_values(t, u.values) == want


def test_status_parity_random_sequential():
    """Statuses match what the oracle reports op-by-op on a causal log."""
    merged, ops = _random_session(11, n_replicas=3, steps=60)
    # oracle: apply sequentially, record per-op outcome
    tree = crdt.init(50)
    want = []
    for op in ops:
        before = len(tree.operations)
        tree = tree.apply(op)
        if len(tree.operations) > before:
            want.append("applied")
        else:
            want.append("already_applied")
    vis, t, p = kernel_visible(ops)
    assert view.statuses(t, p.num_ops) == want


def test_forged_prefix_rejected_exactly():
    """Path validation is EXACT row comparison, not a hash: an op whose
    claimed prefix names the right parent timestamp in the wrong positions
    (or any adversarially-crafted near-miss) must be invalid_path.  Guards
    the removal of the old fixed-base polynomial hash, which a malicious
    peer could collide (ADVICE r1)."""
    ops = [crdt.Add(1, (0,), "a"),           # node at path (1,)
           crdt.Add(2, (1, 0), "b"),         # nested: path (1, 2)
           # forged: claims parent prefix (2,) but node 2's path is (1, 2)
           Add(7, (2, 2), "x"),
           # forged: right length, wrong element
           Add(8, (9, 2, 0), "y")]
    vis, t, p = kernel_visible(ops)
    assert vis == ["a", "b"]
    assert view.statuses(t, p.num_ops)[2:] == \
        ["invalid_path", "invalid_path"]


def test_no_deletes_trace_parity():
    """The static no-deletes fast path must be bit-identical to the
    default trace on an all-adds batch, and materialize must keep it OFF
    the moment a delete is present (merge.host_no_deletes is the single
    definition both call sites share)."""
    merged, ops = _random_session(17, n_replicas=3, steps=50)
    ops = [op for op in ops if not isinstance(op, Delete)]  # all-adds
    p = packed.pack(ops)
    arrs = p.arrays()
    assert merge.host_no_deletes(arrs["kind"])
    import jax
    with jax.enable_x64(True):
        lean = view.to_host(merge._materialize(arrs, None, None, True))
        full = view.to_host(merge._materialize(arrs, None, None, False))
    for f in ("ts", "parent", "depth", "value_ref", "exists", "tombstone",
              "dead", "visible", "doc_index", "order", "visible_order",
              "status"):
        assert np.array_equal(np.asarray(getattr(lean, f)),
                              np.asarray(getattr(full, f))), f
    # a single delete flips the host check off
    with_del = ops + [Delete(ops[0].path[:0] + (ops[0].ts,))]
    p2 = packed.pack(with_del)
    assert not merge.host_no_deletes(p2.arrays()["kind"])


def test_hostile_pos_duplicate_winner_agrees():
    """ADVICE r3: a raw-array producer violating the pos == array-index
    contract must not let the ranked path and the join fallback pick
    different canonical copies of a duplicated timestamp.  Both paths
    share one winner rule — the first ARRAY ROW — so the surfaced
    payload/value_ref/status cannot depend on which construction ran."""
    ops = [Add(1, (0,), "first"), Add(1, (0,), "second"),
           Add(2, (1,), "tail")]
    p = packed.pack(ops)
    arrs = dict(p.arrays())
    hostile = np.asarray(arrs["pos"]).copy()
    hostile[0], hostile[1] = 1, 0       # pos claims row 1 arrived first
    arrs["pos"] = hostile
    t_rank = view.to_host(merge.materialize(arrs))           # ranked path
    t_join = view.to_host(merge.materialize(arrs, hints="join"))
    assert view.visible_values(t_rank, p.values) == \
        view.visible_values(t_join, p.values) == ["first", "tail"]
    assert view.statuses(t_rank, p.num_ops) == \
        view.statuses(t_join, p.num_ops)


def test_verify_hints_audits_rank_and_links():
    """packed.verify_hints (the restore-time host audit, ADVICE r3)
    accepts a pack-produced batch and rejects each corruption class:
    stale ranks, mislinked hints, and a dropped hint whose reference is
    resolvable in-batch."""
    ops = [Add(1, (0,), "a"), Add(2, (1,), "b"), Add(3, (2,), "c"),
           Add(4, (1, 0), "d"), Delete((2,))]
    p = packed.pack(ops)
    assert packed.verify_hints(p)

    import dataclasses as dc

    def mutated(**cols):
        q = dc.replace(p, **{k: np.asarray(v).copy()
                             for k, v in cols.items()})
        return packed.verify_hints(q)

    r = p.ts_rank.copy(); r[0], r[1] = r[1], r[0]
    assert not mutated(ts_rank=r)
    a = p.anchor_pos.copy(); a[a >= 0] = 0
    assert not mutated(anchor_pos=a)
    t = p.target_pos.copy(); t[t >= 0] = -1     # drop a resolvable hint
    assert not mutated(target_pos=t)
    pp = p.parent_pos.copy(); pp[3] = 2         # wrong row for d's parent
    assert not mutated(parent_pos=pp)


def test_verify_hints_rejects_stray_out_of_batch_hint():
    """Property (c): an UNRESOLVABLE reference must carry -1.  The
    exhaustive kernel resolves ``hint >= 0`` without the per-hint ts
    check gather (merge._res_hint_impl check_ts=False), so a stray
    hint on an out-of-batch reference would silently resolve to an
    unrelated node instead of landing NOT_FOUND — verify_hints (run on
    every restore/foreign ingest) must therefore reject it, and auto
    mode must still converge to the oracle via the cond fallback."""
    import dataclasses as dc
    ops = [Add(1, (0,), "a"), Add(2, (1,), "b"),
           Add(6, (5, 0), "x")]          # parent ts 5 not in batch
    p = packed.pack(ops)
    assert packed.verify_hints(p)
    assert p.parent_pos[2] == -1
    sp = p.parent_pos.copy()
    sp[2] = 0                            # stray: points at the ts-1 row
    q = dc.replace(p, parent_pos=sp)
    assert not packed.verify_hints(q)
    # auto mode re-verifies on device: the stray hint fails the link
    # check and the whole batch routes through sort+join — same tree
    # as the untampered batch
    t_ok = view.to_host(merge.materialize(p.arrays(), hints="auto"))
    t_bad = view.to_host(merge.materialize(q.arrays(), hints="auto"))
    assert view.visible_values(t_ok, p.values) == \
        view.visible_values(t_bad, q.values)
    assert view.statuses(t_ok, p.num_ops) == \
        view.statuses(t_bad, q.num_ops)


# -- int32 bit-half discipline (round 5): every i64 scatter runs as two
# i32 half scatters (v5e-emulated i64 scatters measured ~25x an i32
# scatter, SWEEP_TPU_r05_prefix).  These pin the wrap/bias edges: low
# halves >= 2^31 (negative as raw int32), and values adjacent to the
# BIG sentinel's bit pattern.

def test_split_pack_roundtrip_edges():
    import jax
    import jax.numpy as jnp
    vals = np.array([0, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32,
                     2**32 + 2**31, 5 * 2**32 + (2**32 - 1),
                     merge.BIG - 1, merge.BIG], dtype=np.int64)
    with jax.enable_x64(True):     # bare asarray would truncate to i32
        v = jnp.asarray(vals)
        h, l = merge._split_u(v)
        assert np.array_equal(np.asarray(merge._pack_u(h, l)), vals)
        hb, lb = merge._split_ts(v)
        assert np.array_equal(
            np.asarray(merge._pack_biased(hb, lb)), vals)
    # biased halves preserve order as a 2-key comparison
    order = np.lexsort((np.asarray(lb), np.asarray(hb)))
    assert np.array_equal(vals[order], np.sort(vals))


def test_high_low_half_timestamps_converge():
    """Counters >= 2^31 put the ts LOW half in negative int32 territory
    for both the biased (sort keys) and raw (fp planes) splits; the
    kernel (any delivery order — SET semantics) must agree with the
    oracle's causal-order fold exactly there."""
    hi = 2**31  # counter crossing the int32 sign boundary
    ops = [Add(1 * OFFSET + hi, (0,), "a"),
           Add(1 * OFFSET + hi + 1, (1 * OFFSET + hi,), "b"),
           Add(2 * OFFSET + 5, (0,), "c"),
           Add(2 * OFFSET + hi + 7, (2 * OFFSET + 5,), "d"),
           Add(1 * OFFSET + 3, (0,), "e")]
    exp, _ = oracle_visible(ops)       # causal order for the oracle
    for seed in range(6):
        rng = random.Random(seed)
        shuffled = ops[:]
        rng.shuffle(shuffled)
        vis, _, _ = kernel_visible(shuffled)
        assert vis == exp, f"seed {seed}: {vis} != {exp}"


@pytest.fixture(scope="module")
def editor_session_arrs():
    """One random single-replica editing session (appends, nested
    branches, deletes) as packed columns."""
    rng = random.Random(77)
    o = crdt.init(5)
    for i in range(300):
        r = rng.random()
        if r < 0.55:
            o = o.add(f"v{i}")
        elif r < 0.7 and len(o.cursor) < 10:
            o = o.add_branch(f"b{i}")
        elif o.visible_values():
            try:
                o = o.delete(list(o.cursor))
            except (crdt.OperationFailedError, crdt.InvalidPathError):
                pass
    return packed.pack(o.operations_since(0)).arrays()


@pytest.mark.parametrize("k", [2, 4, 8])
def test_session_table_agrees_across_modes_and_opsaxis(
        editor_session_arrs, k):
    """The same batch gives one NodeTable, bit for bit, in all three
    hint modes of the stock kernel and through the ops-axis sharded
    body at mesh width ``k`` (the plane gathers, frame scatters and
    scans of ``merge._finish`` run partitioned there)."""
    from crdt_graph_tpu.parallel import opsaxis
    arrs = editor_session_arrs
    assert arrs["kind"].shape[0] % 8 == 0
    assert np.any(arrs["kind"] == packed.KIND_DELETE)
    fields = ["ts", "parent", "depth", "value_ref", "paths", "exists",
              "tombstone", "dead", "visible", "doc_index", "order",
              "visible_order", "num_nodes", "num_visible", "status"]
    want = view.to_host(merge.materialize(arrs, hints="exhaustive"))
    got = {h: view.to_host(merge.materialize(arrs, hints=h))
           for h in ("auto", "join")}
    got["opsaxis"] = view.to_host(
        opsaxis.materialize(arrs, k=k, hints="exhaustive"))
    for h, t in got.items():
        for f in fields:
            assert np.array_equal(np.asarray(getattr(want, f)),
                                  np.asarray(getattr(t, f))), (h, f)
