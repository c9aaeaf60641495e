"""The reduction from a profiler trace to busy time, executable time
and labelled idle gaps."""
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_clip_by_hand():
    ops = {0: [("fusion.1", 0, 10), ("fusion.2", 5, 10),
               ("all-reduce.3", 30, 10), ("copy", 95, 20)]}
    mods = {0: [("jit__materialize(7)", 0, 40), ("jit_other(2)", 95, 20)]}
    red = tr._reduce(ops, mods, 0, 100)
    assert red["busy_ns"] == 15 + 10 + 5
    assert red["module_ns"] == {"jit__materialize": 40, "jit_other": 5}
    assert red["gaps"] == [(15, 30), (40, 95)]


def test_gaps_are_labelled_by_the_commit_stage_in_progress():
    wall0 = 1000.0
    rec = {"ts": wall0 + 0.010, "total_ms": 8.0,
           "stages_ms": {"fuse": 2.0, "merge": 6.0}}
    gaps = [(2_500_000, 3_500_000), (6_000_000, 7_500_000),
            (20_000_000, 25_000_000), (9_000_000, 9_000_500)]
    out = tr.label_gaps(gaps, 0, wall0, [rec])
    assert out["commit.fuse"] == pytest.approx(0.001)
    assert out["commit.merge"] == pytest.approx(0.0015)
    assert out["no commit in progress"] == pytest.approx(0.005)
    assert out["between ops (gap under 1 ms)"] == pytest.approx(5e-7)


def test_module_names_lose_their_run_ids():
    assert tr.module_name("jit__materialize(123)") == "jit__materialize"
    assert tr.module_name("jit_f") == "jit_f"


def test_recorded_tpu_trace():
    """Three merges of a small chain log through the program's kernel,
    traced on one v5e chip under the benchmark's window annotation
    (PR 22)."""
    red = tr.reduce_file(os.path.join(DATA, "merge_v5e.xplane.pb.gz"))
    assert red["devices"] == 1
    assert 0 < red["busy_ns"] <= red["window_ns"]
    assert set(red["module_ns"]) == {"jit__materialize"}
    assert red["module_ns"]["jit__materialize"] <= red["window_ns"]
    assert sum(red["op_ns"].values()) >= red["busy_ns"]
    gaps = sum(e - s for s, e in red["gaps"])
    assert gaps + red["busy_ns"] == red["window_ns"]
