"""``correct`` comes out true for the program and false for the control
and for each fault a cell can have, with the rest of a run driven as on
the chip (the look for a chip skipped), at sizes a CPU holds.

Faults, each planted where the answer is produced:

- ``unchanged``: a commit is acked but the document's state does not
  advance (the published snapshot is never swapped);
- ``half``: half of each write's ops are left out before the merge;
- ``altered``: one value of each published state is altered;
- ``one_slot``: one value is altered only in the document that rides
  the second slot of each cross-document grouped launch (push).
"""
import json

import pytest

from benchmark import harness

SMALL = {"join64_1M.catchup": {"ops": 64 * 256},
         "nested8_100k.push": {"ops": 2048},
         "nested8_100k.edit_rejoin": {"ops": 2048}}


def _run(cell, seed, **kw):
    return harness.run(cell, seed, 2.0, False, allow_cpu=True,
                       overrides=SMALL[cell], **kw)


def _unchanged(monkeypatch):
    from crdt_graph_tpu.serve import engine

    def no_swap(self, snap):
        return self._snap.age_s()
    monkeypatch.setattr(engine.ServedDoc, "publish_prepared", no_swap)


def _half(monkeypatch):
    from crdt_graph_tpu.serve import engine
    real = engine.ServingEngine._parse

    def half(self, body):
        obj = json.loads(body)
        obj["ops"] = obj["ops"][:max(1, len(obj["ops"]) // 2)]
        return real(self, json.dumps(obj, separators=(",", ":")).encode())
    monkeypatch.setattr(engine.ServingEngine, "_parse", half)


def _altered(monkeypatch):
    from crdt_graph_tpu.serve import snapshot
    real = snapshot.derive

    def altered(*a, **kw):
        snap = real(*a, **kw)
        if len(snap.values) > 1:
            vals = list(snap.values)
            vals[-1] = vals[0]
            snap.values = vals
        return snap
    monkeypatch.setattr(snapshot, "derive", altered)


def _one_slot(monkeypatch):
    from crdt_graph_tpu.parallel import mesh
    from crdt_graph_tpu.serve import scheduler, snapshot
    state = {"slot": 0, "on": False, "hit": 0}
    real_launch = mesh.batched_materialize
    real_finish = scheduler.MergeScheduler._finish_grouped
    real_derive = snapshot.derive

    def launch(*a, **kw):
        state["slot"] = 0
        return real_launch(*a, **kw)

    def finish(self, item, p, table):
        state["on"] = state["slot"] == 1
        state["slot"] += 1
        try:
            real_finish(self, item, p, table)
        finally:
            state["on"] = False

    def derive(*a, **kw):
        snap = real_derive(*a, **kw)
        if state["on"] and len(snap.values) > 1:
            state["hit"] += 1
            vals = list(snap.values)
            vals[-1] = vals[0]
            snap.values = vals
        return snap
    monkeypatch.setattr(mesh, "batched_materialize", launch)
    monkeypatch.setattr(scheduler.MergeScheduler, "_finish_grouped", finish)
    monkeypatch.setattr(snapshot, "derive", derive)
    return state


FAULTS = {"unchanged": _unchanged, "half": _half, "altered": _altered}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_program_is_correct(cell):
    r = _run(cell, 2**33 + 17)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_is_not_correct(cell):
    r = _run(cell, 5, control=True)
    assert not r["correct"]
    assert r["checks"]["value_mismatches"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(SMALL))
def test_fault_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    r = _run(cell, 11)
    assert not r["correct"], (fault, r["checks"])


@pytest.mark.parametrize("seed", [11, 2**32 + 3])
def test_a_fault_in_one_grouped_slot_is_not_correct(seed, monkeypatch):
    state = _one_slot(monkeypatch)
    r = _run("nested8_100k.push", seed)
    assert state["hit"] > 0
    assert not r["correct"], r["checks"]
