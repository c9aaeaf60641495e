"""The delete path's counters through the serving engine: a push's
Delete rows in its kernel merge (``kernel_delete_rows``, counted in
``parallel/opsaxis.routed_materialize``) and those the kernel applied
(``kernel_deletes_applied``, counted in ``TpuTree.finish_packed``),
shown among ``ServingEngine.counters`` (docs/OBSERVABILITY.md)."""
import pytest

from crdt_graph_tpu.codec import json_codec
from crdt_graph_tpu.core.operation import Add, Batch, Delete

OFF = 2 ** 32
KEYS = ("kernel_rows", "kernel_delete_rows", "kernel_deletes_applied")


def _wide(rids, per, doomed):
    """Config 4's shape: each replica's children at the root head, then
    ``doomed`` of them deleted."""
    adds = [Add(r * OFF + c, (0,), c) for r in rids for c in range(1, per + 1)]
    dels = [Delete((r * OFF + c,)) for r in rids for c in range(1, doomed + 1)]
    return adds, dels


@pytest.fixture()
def eng():
    from crdt_graph_tpu.obs import flight as flight_mod
    from crdt_graph_tpu.serve import ServingEngine
    e = ServingEngine(flight=flight_mod.FlightRecorder())
    yield e
    e.close()


def _push(eng, doc, ops):
    c0 = eng.counters.snapshot()
    ok, _ = eng.submit(doc, json_codec.dumps(Batch(tuple(ops))))
    assert ok and eng.flush(60)
    c1 = eng.counters.snapshot()
    return tuple(c1[k] - c0.get(k, 0) for k in KEYS)


def test_a_pushs_deletes_are_counted_in_its_kernel_merge(eng):
    adds, dels = _wide((3, 5, 9, 11), 256, 230)
    assert _push(eng, "d", adds + dels) == (2048, 920, 920)
    doc = eng.get("d")
    assert doc.tree.log_length == 1944
    assert len(doc.snapshot()) == 4 * 26


def test_redelivered_deletes_are_rows_but_not_applied(eng):
    adds, dels = _wide((3, 5), 512, 400)
    assert _push(eng, "d", adds + dels) == (2048, 800, 800)
    more, _ = _wide((7,), 1024, 0)
    # the candidate set is log ∪ delta: the log's 800 Deletes and the
    # 800 sent again, which the kernel absorbs.  A batch applied in
    # part leaves its table unused, so the publish merges the log
    # (2,848 rows, 800 Deletes) once more
    assert _push(eng, "d", more + dels) == (4096 + 4096, 1600 + 800, 0)
    doc = eng.get("d")
    assert doc.tree.log_length == 2848
    assert len(doc.snapshot()) == 2 * 112 + 1024


def test_an_all_adds_push_counts_no_delete_rows(eng):
    adds, _ = _wide((3, 5), 1024, 0)
    assert _push(eng, "d", adds) == (2048, 0, 0)
