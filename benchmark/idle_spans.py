"""Device idle time put down to the program span that was open.

The program's spans (``crdt_graph_tpu.utils.profiling.span``) are
``jax.profiler.TraceAnnotation`` events on the host plane of the same
``.xplane.pb`` that ``trace_reduce`` reads, on the device events'
clock.  This reduction reads every host event named ``serve.*`` or
``engine.*`` with the thread line it is on, takes the device-idle
intervals of the window from ``trace_reduce.reduce_file``, and labels
each idle interval, split at span edges:

- by the innermost span open on a scheduler thread (a line that holds
  ``serve.round``);
- else by the innermost span open on any other thread, as
  ``other:<name>``;
- else ``no span open``.

Gaps under 1 ms keep ``trace_reduce``'s own label.  Time is summed over
the devices and averaged like ``trace_reduce``'s ``idle_gaps``.

``trace_reduce.summarize`` does not call this yet.  Until it does,
run one traced cell with it through ``run.py``'s own entry point:

    python3 benchmark/idle_spans.py --workload <cell> --seed <n> \\
        --seconds <s>

which prints the result line of ``run.py --trace 1`` with
``breakdown.idle_spans`` (the top 10 labels, seconds) and
``breakdown.idle_unspanned_share`` (% of the device-idle time with no
``serve.*`` or ``engine.*`` span open on any thread) added.
"""
from __future__ import annotations

import gzip
import os
import sys
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from benchmark import trace_reduce  # noqa: E402

PREFIXES = ("serve.", "engine.")
ROOT_SPAN = "serve.round"
NO_SPAN = "no span open"
SHORT_LABEL = "between ops (gap under 1 ms)"
TOP = 10

Span = Tuple[int, int, str]


def read_spans(path: str) -> List[List[Span]]:
    """The ``serve.*`` and ``engine.*`` events of each host thread line
    of one trace file, as ``(start_ns, end_ns, name)``."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    lines = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                    ev.name) for ev in line.events
                   if ev.name.startswith(PREFIXES)]
            if evs:
                lines.append(evs)
    return lines


def label_idle(gaps: Sequence[Tuple[int, int]],
               lines: Sequence[Sequence[Span]]) -> Dict[str, int]:
    """Nanoseconds of device idle time by label (see the module
    docstring), summed over the gaps given: ``gaps`` may hold the gaps
    of several devices, overlapping in time."""
    out: Dict[str, int] = defaultdict(int)
    sched = {i for i, evs in enumerate(lines)
             if any(name == ROOT_SPAN for _, _, name in evs)}
    # (time, kind, ...): kind 0 closes, 1 opens; a gap edge carries the
    # change in the number of idle devices
    edges = []
    for s, e in gaps:
        if e - s < trace_reduce.SHORT_GAP_NS:
            out[SHORT_LABEL] += e - s
        elif e > s:
            edges.append((s, 1, -1, 1))
            edges.append((e, 0, -1, -1))
    for i, evs in enumerate(lines):
        for k, (s, e, _) in enumerate(evs):
            edges.append((s, 1, i, k))
            edges.append((e, 0, i, k))
    edges.sort()
    open_spans: Dict[int, Dict[int, Tuple[int, str]]] = defaultdict(dict)
    idle = 0
    prev = None

    def label() -> str:
        best = None
        for i in sched:
            for s, name in open_spans.get(i, {}).values():
                if best is None or s > best[0]:
                    best = (s, name)
        if best is not None:
            return best[1]
        for i, spans in open_spans.items():
            if i in sched:
                continue
            for s, name in spans.values():
                if best is None or s > best[0]:
                    best = (s, name)
        return NO_SPAN if best is None else "other:" + best[1]

    for t, kind, i, k in edges:
        if idle and prev is not None and t > prev:
            out[label()] += (t - prev) * idle
        prev = t
        if i < 0:
            idle += k
        elif kind:
            open_spans[i][k] = (lines[i][k][0], lines[i][k][2])
        else:
            open_spans[i].pop(k, None)
            if not open_spans[i]:
                del open_spans[i]
    return dict(out)


def reduce_file(path: str) -> dict:
    """``idle_spans`` (top labels, seconds per device) and
    ``idle_unspanned_share`` (%) of one trace file; the share is None
    where the trace holds no program span (a program without them)."""
    red = trace_reduce.reduce_file(path)
    lines = read_spans(path)
    ns = label_idle(red["gaps"], lines)
    n_dev = max(1, red["devices"])
    top = sorted(ns.items(), key=lambda kv: -kv[1])[:TOP]
    total = sum(ns.values())
    share = None
    if lines and total:
        share = 100.0 * ns.get(NO_SPAN, 0) / total
    return {"idle_spans": [[k, v / n_dev / 1e9] for k, v in top],
            "idle_unspanned_share": share}


def main(argv=None) -> int:
    from benchmark import run
    summarize = trace_reduce.summarize

    def summarize_with_spans(log_dir, records):
        path = trace_reduce.find_xplane(log_dir)
        red = summarize(log_dir, records)
        red["breakdown"].update(reduce_file(path))
        return red

    trace_reduce.summarize = summarize_with_spans
    return run.main(list(sys.argv[1:] if argv is None else argv)
                    + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
