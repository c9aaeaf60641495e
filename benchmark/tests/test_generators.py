"""The generators, the wire encoder and the reference, against the
program's own codec and oracle at small sizes."""
import random

import pytest

from benchmark import reference
from benchmark.traffic import wire, workloads


def _program_batch(ops):
    from crdt_graph_tpu.core.operation import Add, Batch, Delete
    return Batch(tuple(Add(op[2], tuple(op[1]), op[3]) if op[0] == "add"
                       else Delete(tuple(op[1])) for op in ops))


def _chain_ops(log):
    c = log.cols
    return [("add", (a,), t, v) for a, t, v in
            zip(c["anchor"].tolist(), c["ts"].tolist(), c["val"].tolist())]


def test_wire_is_byte_equal_to_the_program_codec():
    from crdt_graph_tpu.codec import json_codec
    log = workloads.nested_log(300, 4, 5, 16, random.Random(1))
    s = workloads.EditorSession(3, log.branch_paths[2], 0.3,
                                random.Random(2), tag=1)
    ops = log.ops + s.delta(40) + [("add", (0,), 7, "é\"x")]
    assert wire.dumps_ops(ops) == json_codec.dumps(
        _program_batch(ops)).encode()
    chain = workloads.chain_log(4, 64, random.Random(3))
    assert chain.body == json_codec.dumps(
        _program_batch(_chain_ops(chain))).encode()


@pytest.mark.parametrize("seed", [0, 1, 2**33 + 5])
def test_chain_closed_form_equals_reference_and_oracle(seed):
    import crdt_graph_tpu as crdt
    log = workloads.chain_log(8, 400, random.Random(seed))
    ops = _chain_ops(log)
    assert log.expected == reference.visible_values(ops)
    oracle = crdt.init(99).apply(_program_batch(ops)).visible_values()
    assert oracle == log.expected
    assert reference.chain_control_values(log.cols, 50) == \
        reference.control_values(ops) != log.expected


@pytest.mark.parametrize("seed", [0, 7])
def test_nested_reference_equals_oracle(seed):
    import crdt_graph_tpu as crdt
    rng = random.Random(seed)
    log = workloads.nested_log(2000, 8, 8, 64, rng)
    ops = list(log.ops)
    for k in range(6):   # editor sessions with backspaces, interleaved
        s = workloads.EditorSession(k + 1, rng.choice(log.branch_paths),
                                    0.15, random.Random(k), tag=k + 1)
        ops += s.delta(rng.randint(1, 16)) + s.delta(16)
    want = reference.visible_values(ops)
    assert len(want) > 0
    for order in range(3):   # the oracle converges in any causal order
        shuffled = ops if order == 0 else _causal_shuffle(ops, order)
        got = crdt.init(99).apply(_program_batch(shuffled)).visible_values()
        assert got == want
    assert reference.control_values(ops) != want


def _causal_shuffle(ops, seed):
    """Interleave the per-replica streams at random (each stream in
    order), after the skeleton."""
    rng = random.Random(seed)
    streams = {}
    for op in ops[7:]:
        ts = op[2] if op[0] == "add" else op[1][-1]
        streams.setdefault(ts >> 32, []).append(op)
    out = list(ops[:7])
    queues = [list(v) for v in streams.values()]
    while queues:
        q = rng.choice(queues)
        out.append(q.pop(0))
        if not q:
            queues.remove(q)
    return out


def test_same_sizes_for_every_seed():
    a = workloads.nested_log(3000, 8, 8, 64, random.Random(1))
    b = workloads.nested_log(3000, 8, 8, 64, random.Random(2))
    assert a.n_ops == b.n_ops == 3000 and a.ops != b.ops
    c = workloads.chain_log(64, 64 * 100, random.Random(1))
    d = workloads.chain_log(64, 64 * 100, random.Random(2))
    assert c.n_ops == d.n_ops and len(c.body) == len(d.body)
    assert max(c.cols["ts"].max(), d.cols["ts"].max()) < 2**62


def test_comparisons_count_differences():
    assert reference.value_mismatches([1, 2, 3], [1, 2, 3]) == 0
    assert reference.value_mismatches([1, 3, 2], [1, 2, 3]) == 2
    assert reference.value_mismatches([1, 2], [1, 2, 3]) == 1
    log = workloads.chain_log(4, 64, random.Random(3))
    assert reference.opset_mismatches_chain(log.body, log.cols) == 0
    half = wire.dumps_chain(log.cols["anchor"][:32], log.cols["ts"][:32],
                            log.cols["val"][:32])
    assert reference.opset_mismatches_chain(half, log.cols) == 32
    ops = _chain_ops(log)
    assert reference.opset_mismatches(log.body, ops) == 0
    assert reference.opset_mismatches(log.body, ops[:-1]) == 1
