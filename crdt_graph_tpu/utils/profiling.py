"""Profiling and observability harness (SURVEY §5: the reference has none;
the TPU framework owes timing + tracing around its merge path).

- :func:`span` / :func:`span_stats` — named wall-clock spans aggregated
  into a process-wide registry (count, total, max) AND emitted as
  ``jax.profiler.TraceAnnotation`` events, so under a profiler capture
  every span sits on the device trace's clock with its args as event
  stats and its parent given by nesting on its thread.  The serving
  scheduler (serve/scheduler.py), the engine's staged apply
  (engine.py) and the HTTP front end time their stages with it.
- :func:`record` — an interval that starts on one thread and ends on
  another (a ticket's queue wait): registry only.
- :func:`trace` — context manager around ``jax.profiler`` emitting a
  TensorBoard-loadable trace directory.
- :func:`table_stats` — structural summary of a merged NodeTable
  (fan-out, depth, tombstone load) for capacity planning and debugging.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict

import numpy as np

import jax


_spans: Dict[str, Dict[str, float]] = {}
_spans_lock = threading.Lock()


@contextlib.contextmanager
def span(name: str, **args):
    """``with span("serve.merge", doc=d): ...`` — accumulate the block's
    wall time under ``name`` in the process-wide span registry
    (thread-safe; the registry lock is held only for the counter
    update, never across the timed block), and enter a
    ``jax.profiler.TraceAnnotation(name, **args)`` around it.  ``args``
    are small scalars; they land as event stats in a profiler
    capture."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **args):
            yield
    finally:
        record(name, (time.perf_counter() - t0) * 1e3)


def record(name: str, ms: float) -> None:
    """Add one interval of ``ms`` under ``name`` to the span registry —
    for an interval no single thread's block spans (it starts on one
    thread and ends on another), so it has no trace event."""
    with _spans_lock:
        s = _spans.get(name)
        if s is None:
            s = _spans[name] = {"count": 0, "total_ms": 0.0, "max_ms": 0.0}
        s["count"] += 1
        s["total_ms"] += ms
        s["max_ms"] = max(s["max_ms"], ms)


def span_stats(prefix: str = "") -> Dict[str, Dict[str, float]]:
    """Snapshot of the span registry (names starting with ``prefix``),
    with per-span mean derived from count/total."""
    with _spans_lock:
        out = {}
        for name, s in _spans.items():
            if name.startswith(prefix):
                row = dict(s)
                row["mean_ms"] = s["total_ms"] / max(s["count"], 1)
                out[name] = row
        return out


def reset_spans(prefix: str = "") -> None:
    """Drop accumulated spans (names starting with ``prefix``)."""
    with _spans_lock:
        for name in [n for n in _spans if n.startswith(prefix)]:
            del _spans[name]


@contextlib.contextmanager
def trace(log_dir: str):
    """``with trace("/tmp/tb"):`` captures a jax.profiler trace of the
    block into ``log_dir``; the trace stops even when the block
    raises."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def table_stats(table) -> Dict[str, Any]:
    """Structural summary of a (host) NodeTable."""
    exists = np.asarray(table.exists)
    depth = np.asarray(table.depth)[exists]
    parent = np.asarray(table.parent)[exists]
    tomb = np.asarray(table.tombstone)[exists]
    dead = np.asarray(table.dead)[exists]
    n = int(exists.sum())
    if n == 0:
        return {"nodes": 0, "visible": 0}
    fanout = np.bincount(parent)
    return {
        "nodes": n,
        "visible": int(np.asarray(table.num_visible)),
        "tombstones": int(tomb.sum()),
        "dead": int(dead.sum()),
        "max_depth": int(depth.max()),
        "mean_depth": float(depth.mean()),
        "max_fanout": int(fanout.max()),
        "tombstone_ratio": float(tomb.sum() / n),
    }
