"""Reduce a JAX profiler trace of the window to the numbers the metrics
read: device busy and idle time, device time by executable and by
operation, and the idle gaps labelled by what the
server was doing on the host (from its flight records, on the same
clock through the ``bench.window`` annotation).

Reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` alone.
Device planes are named ``/device:TPU:<n>``; on each, the ``XLA Ops``
line holds one event per operation run and the ``XLA Modules`` line
one per executable run.  Busy time is the union of the operation
intervals, clipped to the window, averaged over the devices.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

ANCHOR = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")
SHORT_GAP_NS = 1_000_000
NAME_CHARS = 120           # an HLO op's name is its whole instruction

_state: dict = {}


def start(log_dir: str) -> None:
    """Start the profiler and open the window's anchor annotation."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    ann = jax.profiler.TraceAnnotation(ANCHOR)
    ann.__enter__()
    _state.update(ann=ann, wall0=time.time())


def stop() -> None:
    import jax
    _state.pop("ann").__exit__(None, None, None)
    jax.profiler.stop_trace()


def module_name(name: str) -> str:
    """``jit__materialize(123)`` → ``jit__materialize``."""
    return _SUFFIX.sub("", name)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_file(path: str, window: Optional[Tuple[int, int]] = None
                ) -> dict:
    """The numbers of one trace file (``.xplane.pb``, or gzipped as
    ``.xplane.pb.gz``).  ``window`` (ns, on the trace's
    clock) defaults to the ``bench.window`` annotation, or else the
    span of the device events."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices = []
    anchor = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor = (int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns))
    dev_ops: Dict[int, List[tuple]] = {}
    dev_mods: Dict[int, List[tuple]] = {}
    for i, plane in enumerate(devices):
        ops, mods = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                       for ev in line.events]
            elif line.name == MODULES_LINE:
                mods = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
        dev_ops[i], dev_mods[i] = ops, mods
    if window is None:
        window = anchor
    if window is None:
        starts = [s for ops in dev_ops.values() for _, s, _ in ops]
        ends = [s + d for ops in dev_ops.values() for _, s, d in ops]
        window = (min(starts), max(ends)) if starts else (0, 0)
    lo, hi = window
    return _reduce(dev_ops, dev_mods, lo, hi)


def _clip(s: int, d: int, lo: int, hi: int) -> int:
    return max(0, min(s + d, hi) - max(s, lo))


def _reduce(dev_ops, dev_mods, lo: int, hi: int) -> dict:
    n_dev = max(1, len(dev_ops))
    busy_ns = 0
    op_ns: Dict[str, int] = defaultdict(int)
    mod_ns: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[int, int]] = []
    for i, ops in dev_ops.items():
        iv = _union([(max(s, lo), min(s + d, hi)) for _, s, d in ops
                     if s + d > lo and s < hi])
        busy_ns += sum(e - s for s, e in iv)
        prev = lo
        for s, e in iv:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if hi > prev:
            gaps.append((prev, hi))
        for name, s, d in ops:
            c = _clip(s, d, lo, hi)
            if c:
                op_ns[name] += c
        for name, s, d in dev_mods[i]:
            c = _clip(s, d, lo, hi)
            if c:
                mod_ns[module_name(name)] += c
    return {"devices": len(dev_ops), "window_ns": hi - lo,
            "window_lo_ns": lo,
            "busy_ns": busy_ns / n_dev,
            "op_ns": {k: v / n_dev for k, v in op_ns.items()},
            "module_ns": {k: v / n_dev for k, v in mod_ns.items()},
            "gaps": gaps}


def label_gaps(gaps: List[Tuple[int, int]], lo_ns: int, wall0: float,
               records: List[dict]) -> Dict[str, float]:
    """Seconds of device idle time by what the server was doing then:
    the stage of the commit in progress (stages laid end to end, ending
    at the commit's resolution), or no commit in progress."""
    spans = []
    for r in records:
        end = (r["ts"] - wall0) * 1e9 + lo_ns
        t = end - r["total_ms"] * 1e6
        for stage, ms in r["stages_ms"].items():
            spans.append((t, t + ms * 1e6, f"commit.{stage}"))
            t += ms * 1e6
    spans.sort()
    out: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        if e - s < SHORT_GAP_NS:
            out["between ops (gap under 1 ms)"] += (e - s) / 1e9
            continue
        mid = (s + e) / 2
        name = "no commit in progress"
        for a, b, stage in spans:
            if a <= mid < b:
                name = stage
                break
            if a > mid:
                break
        out[name] += (e - s) / 1e9
    return out


def summarize(log_dir: str, records: List[dict]) -> dict:
    red = reduce_file(find_xplane(log_dir))
    idle = label_gaps(red.pop("gaps"), red["window_lo_ns"],
                      _state.get("wall0", 0.0), records)
    idle = {k: v / max(1, red["devices"]) for k, v in idle.items()}
    top_ops = sorted(red["op_ns"].items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    red["busy_s"] = red["busy_ns"] / 1e9
    red["window_s"] = red["window_ns"] / 1e9
    red["breakdown"] = {
        "device_ops": [[k[:NAME_CHARS], v / 1e9] for k, v in top_ops],
        "idle_gaps": [[k, v] for k, v in top_idle]}
    return red
