"""Per-op serving latency: the incremental-apply benchmark.

The reference applies one op in O(depth·log b + siblings)
(Internal/Node.elm:51-104); the engine's host-mirror delta path must match
that asymptotic — per-op latency that does NOT grow with document size.
This harness replays a config-1-style editor session (interleaved
insert/delete, models/text.py) on top of pre-seeded documents of
increasing size and reports per-op latency percentiles for each.

Run: ``python -m crdt_graph_tpu.bench.incremental``
"""
from __future__ import annotations

import json
import random
import sys
import time
from typing import Dict, List

from ..core.operation import Add, Batch
from ..models.text import TextBuffer


def seed_document(buf: TextBuffer, size: int, rid: int = 1) -> None:
    """Bulk-load ``size`` characters as one remote batch (kernel path for
    big sizes — exactly how a replica bootstraps from anti-entropy)."""
    ops, prev = [], 0
    for i in range(1, size + 1):
        ts = rid * 2**32 + i
        ops.append(Add(ts, (prev,), "x"))
        prev = ts
    buf.apply(Batch(tuple(ops)))


def editor_replay(buf: TextBuffer, n_ops: int, seed: int = 7) -> List[float]:
    """Interleaved single-char inserts (70%) and deletes (30%) at random
    indices; returns per-op wall times."""
    rng = random.Random(seed)
    times: List[float] = []
    for k in range(n_ops):
        n = len(buf)
        t0 = time.perf_counter()
        if n and rng.random() < 0.3:
            buf.delete(rng.randrange(n))
        else:
            buf.insert(rng.randrange(n + 1), chr(97 + k % 26))
        times.append(time.perf_counter() - t0)
    return times


def percentiles(times: List[float]) -> Dict[str, float]:
    s = sorted(times)
    return {
        "p50_us": round(s[len(s) // 2] * 1e6, 1),
        "p99_us": round(s[int(len(s) * 0.99)] * 1e6, 1),
    }


def bulk_deltas(buf: TextBuffer, doc_size: int, delta: int,
                rid: int = 3) -> float:
    """One bulk (> DELTA_THRESHOLD) remote batch of ``delta`` causal
    appends onto a ``doc_size`` document; returns seconds.  This is the
    serving cliff VERDICT r2 weak-3 flagged: before round 3 every bulk
    apply re-materialised the WHOLE log (O(doc)); the host-first bulk
    path makes it O(delta)."""
    base = buf.last_replica_timestamp(rid) & (2**32 - 1)
    ops, prev = [], 0
    for i in range(1, delta + 1):
        ts = rid * 2**32 + base + i
        ops.append(Add(ts, (prev,), "y"))
        prev = ts
    t0 = time.perf_counter()
    buf.apply(Batch(tuple(ops)))
    return time.perf_counter() - t0


def run_bulk(doc_sizes=(10_000, 100_000, 1_000_000),
             deltas=(1_000, 10_000)) -> list:
    """Bulk-apply cost vs document size (VERDICT r2 task 6 artifact)."""
    results = []
    for size in doc_sizes:
        buf = TextBuffer(70, engine="tpu")
        seed_document(buf, size)
        len(buf)
        for delta in deltas:
            secs = bulk_deltas(buf, size, delta, rid=3 + deltas.index(delta))
            row = {"doc_size": size, "bulk_delta": delta,
                   "apply_ms": round(secs * 1e3, 1),
                   "us_per_op": round(secs / delta * 1e6, 2)}
            results.append(row)
            print(json.dumps(row), flush=True)
    return results


def run(doc_sizes=(1_000, 10_000, 100_000), n_ops: int = 1_000) -> list:
    results = []
    for size in doc_sizes:
        # editor replica id ABOVE the seed's: this reference's clock is
        # per-replica counters (not Lamport), so a LOWER-id editor's
        # inserts legitimately skip-scan past every higher-ts sibling to
        # their right (Internal/Node.elm:93-104) — an O(suffix) semantic
        # cost, not an implementation one.  Realistic collaboration has
        # interleaved ids; benching the higher-id editor isolates the
        # engine's own per-op cost.
        buf = TextBuffer(70, engine="tpu")
        seed_document(buf, size)
        len(buf)                        # warm the path cache / mirror
        stats = percentiles(editor_replay(buf, n_ops))
        row = {"doc_size": size, "n_ops": n_ops, **stats}
        results.append(row)
        print(json.dumps(row), flush=True)
    return results


if __name__ == "__main__":
    # host-path benchmark: pin to CPU so it never holds the chip (one
    # process per chip); device numbers come from the TPU sweep instead
    from ..utils import hostenv
    hostenv.scrub_tpu_env(1)
    import jax
    jax.config.update("jax_platforms", "cpu")
    if len(sys.argv) > 1 and sys.argv[1] == "bulk":
        run_bulk()
    else:
        sizes = [int(a) for a in sys.argv[1:]] or None
        run(*((sizes,) if sizes else ()))
