"""The readers of the four-chip cell's per-layer metrics, on a synthetic
run: which trace events count as collectives, the slice roofline's
arithmetic, and the route's row share and placement span."""
import types

import pytest

from benchmark import harness, roofline

GATHER = ("%all-gather.124 = u32[4,262145,5]{1,2,0:T(8,128)S(1)} "
          "all-gather(u32[262145,5]{1,0:T(8,128)} %copy.77), channel_id=1")
PSUM = ("%psum.6 = s32[]{:T(128)} all-reduce(s32[]{:T(128)} %bitcast.61), "
        "channel_id=1, to_apply=%region_61.64")
PERMUTE_START = ("%collective-permute-start.3 = (s32[3]{0:T(128)}, "
                 "s32[3]{0:T(128)}, u32[]{:S(2)}, u32[]{:S(2)}) "
                 "collective-permute-start(s32[3]{0:T(128)} %pad.13)")
PERMUTE_DONE = ("%collective-permute-done.3 = s32[3]{0:T(128)} "
                "collective-permute-done((s32[3]{0:T(128)}) "
                "%collective-permute-start.3)")
# consumers of a collective's result, and ops whose shapes hold "(":
# none is a collective
FUSION = ("%fusion.3 = u32[262145,5]{1,0:T(8,128)} fusion(u32[4,262145,5]"
          "{1,2,0:T(8,128)S(1)} %all-gather.124), kind=kLoop")
COPY = ("%copy-start.26 = (u32[8192]{0:T(1024)}, u32[]{:S(2)}) "
        "copy-start(u32[8192]{0:T(1024)S(1)} %custom-call)")


def _ctx(trace=None, counters=None, spans=None, commits=(), n_writes=2,
         ops=1000, chips=4, kind="TPU v5 lite"):
    writes = [types.SimpleNamespace(ok=True, n_ops=ops)
              for _ in range(n_writes)]
    writes.append(types.SimpleNamespace(ok=False, n_ops=0))
    return harness.Ctx(cell={"chips": chips}, config={}, traffic="catchup",
                       window_s=51.0, setup_s=1.0, writes=writes, reads=[],
                       spans=spans or {}, counters=counters or {},
                       commits=list(commits), compiles=0, trace=trace,
                       device={"kind": kind})


def test_collective_opcodes_and_both_halves_count():
    mod = harness.load_reader("collective_ms.catchup")
    for name in (GATHER, PSUM, PERMUTE_START, PERMUTE_DONE,
                 "all-reduce-start.2", "all-gather-done", "reduce-scatter.1",
                 "all-to-all.4"):
        assert mod.is_collective(name), name
    for name in (FUSION, COPY, "fusion.2", "%custom-call = u32[8] "
                 "custom-call(s64[8] %ops__ts__.1)"):
        assert not mod.is_collective(name), name
    trace = {"op_ns": {GATHER: 3e6, PSUM: 1e6, PERMUTE_START: 2e5,
                       PERMUTE_DONE: 8e5, FUSION: 50e6, COPY: 9e6}}
    # 5 ms of collectives over 2 acked writes (the refused one is not
    # counted)
    assert mod.read(_ctx(trace)) == pytest.approx(2.5)
    assert mod.read(_ctx({"op_ns": {FUSION: 1e6}})) is None
    assert mod.read(_ctx(None)) is None


def test_slice_roofline_divides_by_every_chip():
    mod = harness.load_reader("merge_slice_hbm_roofline.catchup")
    one = harness.load_reader("merge_hbm_roofline.catchup")
    trace = {"module_ns": {"jit__opsaxis_materialize": 4e6,
                           "jit__materialize": 1e6, "jit_other": 7e6}}
    want = 100.0 * (2 * roofline.merge_bytes(1000, 1) / (4 * 819e9)) / 5e-3
    assert mod.read(_ctx(trace)) == pytest.approx(want)
    # the one-chip share of the same run reads four times higher
    assert one.read(_ctx(trace)) == pytest.approx(4 * want)
    assert mod.read(_ctx(trace, chips=1)) == pytest.approx(4 * want)
    assert mod.read(_ctx({"module_ns": {"jit_other": 1e6}})) is None
    assert mod.read(_ctx(None)) is None
    with pytest.raises(KeyError):
        mod.read(_ctx(trace, kind="TPU v9 imaginary"))


def test_row_share_reads_the_route_counters():
    mod = harness.load_reader("opsaxis_row_share.catchup")
    rows = {"opsaxis_rows": 5 * 2 ** 20 + 2 ** 18,
            "kernel_rows": 5 * 2 ** 20 + 2 ** 18 + 2 ** 17}
    assert mod.read(_ctx(counters=rows)) == pytest.approx(
        100 * 5.25 / 5.375)
    assert mod.read(_ctx(counters={"kernel_rows": 2 ** 17})) == 0.0
    # a program that does not count them
    assert mod.read(_ctx(counters={"fused_batches": 3})) is None


def test_place_span_per_committed_commit():
    mod = harness.load_reader("opsaxis_place_ms.catchup")
    commits = [{"outcome": "committed"}] * 4 + [{"outcome": "error"}]
    spans = {"opsaxis.place": {"count": 28, "total_ms": 84.0}}
    assert mod.read(_ctx(spans=spans, commits=commits)) == 21.0
    assert mod.read(_ctx(spans={}, commits=commits)) is None
