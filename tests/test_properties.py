"""Convergence property suite — the framework's race-detection strategy
(SURVEY §5): the merge is a semilattice join, so it must be invariant
under delivery order, duplication, and partitioning.  Each property is
checked over randomized causally-valid multi-replica logs."""
import random

import numpy as np
import pytest

import crdt_graph_tpu as crdt
from crdt_graph_tpu import engine
from crdt_graph_tpu.codec import packed
from crdt_graph_tpu.ops import merge, view

from test_merge_kernel import _random_session

SEEDS = [41, 42, 43]


def table_fingerprint(t):
    """Everything order-dependent about a converged table."""
    order = np.asarray(t.order)[:int(t.num_nodes)]
    return (
        [int(x) for x in np.asarray(t.ts)[order]],
        [bool(b) for b in np.asarray(t.tombstone)[order]],
        [bool(b) for b in np.asarray(t.dead)[order]],
    )


@pytest.mark.parametrize("seed", SEEDS)
def test_permutation_invariance(seed):
    _, ops = _random_session(seed, n_replicas=4, steps=90)
    p0 = packed.pack(ops)
    want = table_fingerprint(view.to_host(merge.materialize(p0.arrays())))
    rng = random.Random(seed * 7)
    for _ in range(3):
        perm = ops[:]
        rng.shuffle(perm)
        p = packed.pack(perm)
        got = table_fingerprint(view.to_host(merge.materialize(p.arrays())))
        assert got == want


@pytest.mark.parametrize("seed", SEEDS)
def test_duplication_invariance(seed):
    """log ++ log materialises identically to log (idempotent join)."""
    _, ops = _random_session(seed, n_replicas=3, steps=60)
    p1 = packed.pack(ops)
    p2 = packed.concat(packed.pack(ops), packed.pack(ops))
    f1 = table_fingerprint(view.to_host(merge.materialize(p1.arrays())))
    f2 = table_fingerprint(view.to_host(merge.materialize(p2.arrays())))
    assert f1 == f2


@pytest.mark.parametrize("seed", SEEDS)
def test_partition_tree_merge(seed):
    """Splitting the log into k parts and joining them pairwise in any
    tree shape equals materialising the whole (associativity)."""
    _, ops = _random_session(seed, n_replicas=3, steps=75)
    want = table_fingerprint(
        view.to_host(merge.materialize(packed.pack(ops).arrays())))
    rng = random.Random(seed)
    k = 4
    cuts = sorted(rng.sample(range(1, len(ops)), k - 1))
    parts = [packed.pack(ops[a:b])
             for a, b in zip([0] + cuts, cuts + [len(ops)])]
    while len(parts) > 1:
        i = rng.randrange(len(parts) - 1)
        parts[i:i + 2] = [packed.concat(parts[i], parts[i + 1])]
    got = table_fingerprint(view.to_host(merge.materialize(
        parts[0].arrays())))
    assert got == want


@pytest.mark.parametrize("seed", [51, 52])
def test_gossip_with_loss_and_redelivery_converges(seed):
    """Engine-level network simulation: replicas gossip deltas over a lossy
    channel that drops, duplicates, and reorders messages; pull-based
    anti-entropy repairs the gaps; all replicas converge."""
    rng = random.Random(seed)
    n = 4
    trees = [engine.init(r + 1) for r in range(n)]
    inflight = []   # (dest, delta)
    for step in range(150):
        r = rng.randrange(n)
        t = trees[r]
        roll = rng.random()
        try:
            if roll < 0.55:
                t.add(f"{r}:{step}")
                # broadcast the delta — unreliably
                for d in range(n):
                    if d != r and rng.random() < 0.7:
                        inflight.append((d, t.last_operation))
                        if rng.random() < 0.3:   # duplicate delivery
                            inflight.append((d, t.last_operation))
            elif roll < 0.75 and inflight:
                i = rng.randrange(len(inflight))   # arbitrary reordering
                dest, delta = inflight.pop(i)
                try:
                    trees[dest].apply(delta)
                except crdt.CRDTError:
                    pass                            # causality gap: dropped
            else:
                # anti-entropy pull from a random peer
                peer = rng.randrange(n)
                if peer != r:
                    since = t.last_replica_timestamp(peer + 1)
                    t.apply(trees[peer].operations_since(since))
        except crdt.CRDTError:
            pass
    # final repair: full mesh sync twice (second pass covers transitive ops)
    for _ in range(2):
        for i in range(n):
            for j in range(n):
                if i != j:
                    trees[i].apply(trees[j].operations_since(0))
    views = [t.visible_values() for t in trees]
    assert all(v == views[0] for v in views[1:])
    assert views[0]   # something actually happened


def test_checkpoint_packed_roundtrip(tmp_path):
    _, ops = _random_session(44, n_replicas=3, steps=50)
    t = engine.init(5)
    t.apply(crdt.Batch(tuple(ops)))
    path = str(tmp_path / "snap.npz")
    t.checkpoint_packed(path)
    back = engine.TpuTree.restore_packed(path)
    assert back.visible_values() == t.visible_values()
    assert back.timestamp == t.timestamp
    assert back.log_length == t.log_length
    # the restored replica keeps replicating
    back.add("after-restore")
    assert "after-restore" in back.visible_values()


def test_table_stats():
    from crdt_graph_tpu.utils import table_stats
    ops = [crdt.Add(1, (0,), "a"), crdt.Add(2, (1, 0), "b"),
           crdt.Add(3, (1,), "c"), crdt.Delete((3,))]
    p = packed.pack(ops)
    st = table_stats(view.to_host(merge.materialize(p.arrays())))
    assert st["nodes"] == 3 and st["visible"] == 2
    assert st["tombstones"] == 1 and st["max_depth"] == 2


def test_trace_starts_and_stops_profiler(monkeypatch, tmp_path):
    """profiling.trace wraps the block in start_trace/stop_trace, and
    stops the trace even when the block raises."""
    import jax

    from crdt_graph_tpu.utils import profiling

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    with profiling.trace(str(tmp_path)):
        pass
    assert calls == [("start", str(tmp_path)), ("stop",)]
    calls.clear()
    with pytest.raises(RuntimeError):
        with profiling.trace(str(tmp_path)):
            raise RuntimeError("block failed")
    assert calls == [("start", str(tmp_path)), ("stop",)]


def test_distributed_single_host_mesh():
    from crdt_graph_tpu.parallel import distributed
    distributed.initialize(num_processes=1)   # no-op
    m = distributed.global_device_mesh(n_ops=2)
    assert m.shape["ops"] == 2
    assert m.shape["docs"] * 2 == len(__import__("jax").devices())

def test_checkpoint_packed_exact_path_and_last_operation(tmp_path):
    # exact path (no .npz suffix appended) and last_operation preserved
    t = engine.init(4).add("x")
    path = str(tmp_path / "snapshot.bin")
    t.checkpoint_packed(path)
    import os
    assert os.path.exists(path)
    back = engine.TpuTree.restore_packed(path)
    assert back.last_operation == t.last_operation
    assert back.last_operation != crdt.Batch(())


def test_distributed_explicit_cluster_failure_raises():
    from crdt_graph_tpu.parallel import distributed
    with pytest.raises(Exception):
        distributed.initialize("256.0.0.1:1", num_processes=2, process_id=5)


def test_restore_repairs_stale_vouch(tmp_path):
    """ADVICE r3: hints_vouched rides in the same npz as the hint columns
    it vouches for, so restore_packed re-verifies on host.  A tampered
    checkpoint (mislinked parent_pos under a True vouch) must not reach
    the cond-free exhaustive mode with the corrupt columns — the restore
    audit catches it and REBUILDS the hints (keeping them corrupt would
    cost the sort+join fallback on every later merge), so the restored
    tree is both correct and back on the fast path."""
    _, ops = _random_session(45, n_replicas=3, steps=40)
    t = engine.init(6)
    t.apply(crdt.Batch(tuple(ops)))
    path = str(tmp_path / "snap.npz")
    t.checkpoint_packed(path)

    def tamper(mutate):
        z = dict(np.load(path))
        mutate(z)
        with open(path, "wb") as f:
            np.savez_compressed(f, **z)
        back = engine.TpuTree.restore_packed(path)
        assert back._packed.hints_vouched
        assert packed.verify_hints(back._packed)   # repaired, not trusted
        assert back.visible_values() == t.visible_values()

    def mislink(z):
        z["parent_pos"][z["parent_pos"] >= 0] = 0

    def rank_swap(z):
        # two rows with DISTINCT ranks (duplicate deliveries share a
        # rank, and swapping equal ranks would be a no-op tamper)
        adds = np.nonzero(z["ts_rank"] >= 0)[0]
        r = z["ts_rank"][adds]
        j = int(np.nonzero(r != r[0])[0][0])
        a, b = adds[0], adds[j]
        z["ts_rank"][a], z["ts_rank"][b] = z["ts_rank"][b], z["ts_rank"][a]

    tamper(mislink)
    t.checkpoint_packed(path)
    tamper(rank_swap)


def test_restore_reads_old_format_checkpoints(tmp_path):
    """r3-format checkpoints carried FULL-CAPACITY columns, an encoded
    last_operation blob, and no ts_rank file; restore must still read
    them (pad/span/rank branches all have a legacy side)."""
    import json as _json
    from crdt_graph_tpu.codec import json_codec

    t = engine.init(8)
    for i in range(5):
        t.add(f"w{i}")
    p = t._ensure_packed()
    meta = {
        "replica": 8, "timestamp": t.timestamp,
        "cursor": list(t.cursor),
        "replicas": {str(k): v for k, v in t._replicas.items()},
        "max_depth": 16, "num_ops": p.num_ops,
        "last_operation": json_codec.encode(t.last_operation),
        "hints_vouched": True,
    }
    path = str(tmp_path / "old.npz")
    with open(path, "wb") as f:
        np.savez_compressed(                      # full capacity, no rank
            f, kind=p.kind, ts=p.ts, parent_ts=p.parent_ts,
            anchor_ts=p.anchor_ts, depth=p.depth, paths=p.paths,
            value_ref=p.value_ref, pos=p.pos,
            parent_pos=p.parent_pos, anchor_pos=p.anchor_pos,
            target_pos=p.target_pos,
            values=np.frombuffer(_json.dumps(p.values).encode(), np.uint8),
            meta=np.frombuffer(_json.dumps(meta).encode(), np.uint8))
    back = engine.TpuTree.restore_packed(path)
    assert back.visible_values() == t.visible_values()
    assert back.last_operation == t.last_operation
    assert back.timestamp == t.timestamp
    back.add("after")
    assert "after" in back.visible_values()
