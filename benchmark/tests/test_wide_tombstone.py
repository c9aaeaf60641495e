"""The ``wide_tombstone`` shape against the program's own codec and
oracle at a small size."""
import random

import pytest

from benchmark import reference
from benchmark.tests.test_generators import _program_batch
from benchmark.traffic import generator, wire


@pytest.mark.parametrize("seed", [0, 3, 2**33 + 7])
def test_wide_tombstone_reference_equals_oracle(seed):
    import crdt_graph_tpu as crdt
    shape = generator.find_module("shapes", "wide_tombstone")
    config = {"replicas": 32, "adds": 32 * 64, "delete_share": 0.9}
    log = shape.make_log(config, random.Random(seed))
    ops = log.ops
    assert log.n_ops == len(ops) == 32 * 64 + 32 * 57
    assert log.body == wire.dumps_ops(ops)
    want = reference.visible_values(ops)
    assert len(want) == 32 * (64 - 57)
    oracle = crdt.init(99).apply(_program_batch(ops)).visible_values()
    assert oracle == want
    assert reference.control_values(ops) != want
