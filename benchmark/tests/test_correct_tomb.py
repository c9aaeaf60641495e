"""``tomb32_1M.catchup`` on the CPU at a small size (32 replicas x 64
adds, 3,872 ops a push, still past the kernel's bar): ``correct`` is
true for the program and false for the control and for each fault,
and the push's Deletes reach the kernel and take effect there.

Besides the faults of ``test_correct.py``, one of the delete path:

- ``deletes_ignored``: the kernel's static no-deletes promise is made
  for every batch (``merge.host_no_deletes`` forced to True), so the
  trace drops the Deletes and each ack counts fewer ops applied than
  the write sent.
"""
import pytest

from benchmark import harness
from benchmark.tests import test_correct

CELL = "tomb32_1M.catchup"
SMALL = {"adds": 32 * 64}
DELETES = 32 * int(64 * 0.9)    # a push's Deletes


def _run(seed, trace=False, **kw):
    return harness.run(CELL, seed, 2.0, trace, allow_cpu=True,
                       overrides=SMALL, **kw)


def _counts():
    from crdt_graph_tpu import engine
    from crdt_graph_tpu.parallel import opsaxis
    return {**opsaxis.route_counts(), **engine.view_counts()}


def _deletes_ignored(monkeypatch):
    from crdt_graph_tpu.ops import merge
    monkeypatch.setattr(merge, "host_no_deletes", lambda kind: True)


FAULTS = dict(test_correct.FAULTS, deletes_ignored=_deletes_ignored)


def test_program_is_correct_and_its_deletes_reach_the_kernel():
    c0 = _counts()
    r = _run(2**33 + 29, trace=True)
    c1 = _counts()
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    # every push, the warm-up's among them, is one kernel merge of a
    # fresh document whose Deletes all apply
    pushes = r["attempted"] + 1
    for k in ("kernel_delete_rows", "kernel_deletes_applied"):
        assert c1[k] - c0[k] == DELETES * pushes, k
    bucket = (c1["kernel_rows"] - c0["kernel_rows"]) // pushes
    assert bucket >= 3872
    share = r["metrics"]["kernel_delete_share.catchup"]["value"]
    assert share == pytest.approx(100 * DELETES / bucket)


def test_control_is_not_correct():
    r = _run(5, control=True)
    assert not r["correct"]
    assert r["checks"]["value_mismatches"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    r = _run(11)
    assert not r["correct"], (fault, r["checks"])
