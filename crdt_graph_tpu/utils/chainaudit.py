"""Trace-time chain auditor: counts and PRICES the M-wide memory ops of
a jitted function — the merge kernel's CI-pinned performance budget.

The round-5 on-chip cost model (docs/TPU_PROFILE.md §3-4; measured on
the kernel as it stood before PRs 1-20) is: every 1M-wide random-access memory op — gather,
scatter, sort, scan — costs ~6 ms of device time on v5e, and the merge
kernel is a dependency chain of them.  Round 6 pinned the raw count
(≤16); round 7 (ISSUE 3) lowers the budget to ≤10 and upgrades the
model from a raw count to a WIDTH-WEIGHTED cost:

- **fast_path** (the CI budget, ≤10): M-wide memory ops on the
  production fast path, counted exactly as before (cheapest cond
  branches, 0-trip loops).  An op is M-wide when its random/serial
  access width reaches ``threshold`` (default: a quarter of the widest
  input axis).
- **modeled_ms_fast**: each fast-path M-wide op bills
  ``MODELED_MS_PER_OP × max(1, cost_width / width_ref)`` — a T = 2M
  tour pass costs twice an M-wide one (the r5 scale sweep measured the
  per-op cost linear in width ABOVE ~1M; docs/TPU_PROFILE.md §3).  A
  ``pallas_call``'s cost width is its output ROW sweep (payload lanes
  are free, like any other op's payload width): one fused kernel
  prices like one serialized pass — the claim prims rows 31-33 are
  staged to confirm on chip.
- **compact_risk_ms**: the S_CAP/R_CAP-compacted stages (width in
  [compact_floor, threshold)) are billed at the CONSERVATIVE fixed
  ~6 ms each and reported separately.  Whether a 32k-wide op really
  costs the fixed ~6 ms (pure per-HLO overhead) or ~0.2 ms (linear in
  width) is the one open model cell — prims rows 25-27 (staged,
  scripts/probe_prims.py) decide it; until measured the exposure is
  DISCLOSED here rather than silently assumed zero.  Fast-path loop
  bodies still bill 0 trips (fixpoint loops; per-trip costs stay
  visible in ``rows``).

Counting rules otherwise unchanged from round 6: counted primitives
are ``gather``, every ``scatter`` variant, ``sort``, the scans
(``cumsum``/``cummax``/...), and ``pallas_call`` (ONE op — that is the
point of fusing); elementwise ops, reductions, concats/pads/slices are
free.  ``cond``: fast path takes the cheapest branch, ``static`` the
most expensive.  ``while``: fast 0 trips, static 1.

Run as a module for the audit table of any config:

    python -m crdt_graph_tpu.utils.chainaudit [config_id ...]
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

# the serialized-access primitives the cost model bills (~6 ms each at
# 1M width on v5e)
_SCATTERS = ("scatter", "scatter-add", "scatter-min", "scatter-max",
             "scatter-mul", "scatter-apply")
_SCANS = ("cumsum", "cummax", "cumprod", "cumlogsumexp")
_CALLS = ("pjit", "closed_call", "core_call", "remat", "remat2",
          "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
          "checkpoint")
# cross-device collectives (v3): not billed as memory ops — their cost
# currency is BYTES MOVED, accounted separately as summed output bytes
# per device (``collective_bytes``) and CI-pinned for the ops-axis
# sharded trace (parallel/opsaxis.py, tests/test_chain_audit.py)
_COLLECTIVES_P = ("all_gather", "ppermute", "psum", "pmin", "pmax",
                  "all_to_all", "reduce_scatter")

MODELED_MS_PER_OP = 6.0   # round-5 chip primitives, while-loop row

# CI budget: fast-path M-wide memory ops on the production (device)
# trace — round 6 pinned 16; the round-7 fusions bring the trace under
# this (tests/test_chain_audit.py asserts both traces' budgets)
FAST_PATH_BUDGET = 10
# the lax/CPU fallback trace keeps the sibling machinery and split
# scans the pallas kernels fuse on TPU
FAST_PATH_BUDGET_LAX = 12
# acceptance (ISSUE 3): width-weighted modeled ms of the fast path
MODELED_MS_CAP = 70.0


@dataclasses.dataclass
class ChainAudit:
    """Result of :func:`count_mwide`.

    ``fast_path``: M-wide memory ops on the production fast path
    (cheapest cond branches, 0-trip loops) — the CI-pinned budget
    number.  ``static``: the most expensive single execution.
    ``modeled_ms_fast``: width-weighted cost of the fast path (see
    module docstring).  ``compact_fast``/``compact_risk_ms``: count and
    conservative fixed-cost exposure of the compacted sub-threshold
    stages on the fast path.  ``rows``: (path, primitive, width,
    cost_ms, note) per counted op, fast path first.
    """
    fast_path: int
    static: int
    threshold: int
    rows: List[Tuple[str, str, int, float, str]]
    width_ref: int = 0
    compact_floor: int = 0
    # v3: which sibling-crowding leg the trace compiled
    # (merge.crowding_hinted — "hinted" = host pre-pass columns skipped
    # the scatter-add+gather+cumsum trio, "counted" = device counting)
    crowding_leg: str = ""

    @property
    def modeled_ms_fast(self) -> float:
        # scan-body rows are fast-path work too (their cost already
        # carries the xlength multiplier from the counter)
        return round(sum(c for _, _, _, c, note in self.rows
                         if note in ("fast", "scan-body")), 1)

    @property
    def compact_fast(self) -> int:
        return sum(1 for _, _, _, _, note in self.rows
                   if note == "compact")

    # -- v3: sharded-trace accounting (parallel/opsaxis.py) ---------------

    @property
    def shard_width(self) -> int:
        """Widest billed memory op inside any shard_map body, fast
        path + compacted stages (slow branches — the single-device
        fallbacks — exempt): the per-shard width the ops-axis budget
        gate pins at ceil(M/k) + halo."""
        return max((w for path, _, w, _, note in self.rows
                    if "[shard]" in path and
                    note in ("fast", "compact", "scan-body")),
                   default=0)

    @property
    def collective_bytes(self) -> int:
        """Summed collective OUTPUT bytes per device on the fast path
        (the counting rule the documented opsaxis bound uses)."""
        return sum(w for _, _, w, _, note in self.rows
                   if note == "collective")

    @property
    def collective_count(self) -> int:
        return sum(1 for _, _, _, _, note in self.rows
                   if note == "collective")

    @property
    def compact_risk_ms(self) -> float:
        return round(self.compact_fast * MODELED_MS_PER_OP, 1)

    def table(self) -> str:
        lines = [f"threshold {self.threshold} | fast_path "
                 f"{self.fast_path} | static {self.static} | modeled "
                 f"{self.modeled_ms_fast} ms | compact {self.compact_fast}"
                 f" ops (+{self.compact_risk_ms} ms risk)"]
        for path, prim, width, cost, note in self.rows:
            lines.append(f"  {prim:14s} {width:>10d} {cost:6.1f}ms "
                         f" {note:10s} {path}")
        return "\n".join(lines)

    def summary(self) -> dict:
        """The bench-facing stats record (bench.py / runner.py emit it
        in every JSON row so the perf trajectory tracks the model even
        when the round-end bench falls back to CPU)."""
        out = {
            "fast_path": self.fast_path,
            "static": self.static,
            "modeled_ms": self.modeled_ms_fast,
            "compact_risk_ms": self.compact_risk_ms,
            "budget": FAST_PATH_BUDGET,
            "ok": bool(self.fast_path <= FAST_PATH_BUDGET and
                       self.modeled_ms_fast <= MODELED_MS_CAP),
        }
        if self.crowding_leg:
            out["crowding_leg"] = self.crowding_leg
        return out


def _aval_size(v) -> int:
    try:
        return int(np.prod(v.aval.shape)) if v.aval.shape else 1
    except Exception:
        return 0


def _width(eqn) -> int:
    """The op's random/serial-access width under the cost model."""
    name = eqn.primitive.name
    if name == "gather":
        idx = eqn.invars[1]
        shape = idx.aval.shape
        return int(np.prod(shape[:-1])) if len(shape) else 1
    if name in _SCATTERS:
        idx = eqn.invars[1]
        shape = idx.aval.shape
        return int(np.prod(shape[:-1])) if len(shape) else 1
    if name == "sort":
        dim = eqn.params.get("dimension", 0)
        return int(eqn.invars[0].aval.shape[dim])
    if name in _SCANS:
        ax = eqn.params.get("axis", 0)
        return int(eqn.invars[0].aval.shape[ax])
    if name == "pallas_call":
        return max((_aval_size(v) for v in eqn.outvars), default=0)
    return max((_aval_size(v) for v in eqn.invars), default=0)


def _sub_jaxprs(params: Dict[str, Any]):
    from jax._src import core as jcore
    for v in params.values():
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jcore.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jcore.Jaxpr):
                    yield x


def _cost_width(eqn, width: int) -> int:
    """The width the cost model scales with: a pallas_call's output ROW
    sweep (max leading output dim — lanes are payload of one gathered
    row), every other op's random/serial-access width.  EXCEPT
    sequential-scan kernels ("scan" in the kernel name, e.g.
    ops/tour_scan's ``tour_scan_prefix``): their lanes ARE serially
    swept stream elements, so they bill by total output size — a fused
    T + Kw·M prefix sweep prices like ~3 M-wide passes until prims
    rows 32-34 measure it cheaper."""
    if eqn.primitive.name != "pallas_call":
        return width
    info = eqn.params.get("name_and_src_info")
    if "scan" in (getattr(info, "name", "") or ""):
        return max((_aval_size(v) for v in eqn.outvars), default=width)
    dims = [int(v.aval.shape[0]) for v in eqn.outvars
            if getattr(v.aval, "shape", ())]
    return max(dims, default=width)


def _count(jaxpr, threshold: int, compact_floor: int, width_ref: int,
           path: str, note: str,
           rows: List[Tuple[str, str, int, float, str]]
           ) -> Tuple[int, int]:
    fast = static = 0
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        here = f"{path}.{i}:{name}"
        if name == "cond":
            branches = eqn.params["branches"]
            counts = []
            for bi, br in enumerate(branches):
                sub_rows: List[Tuple[str, str, int, float, str]] = []
                f, s = _count(br.jaxpr, threshold, compact_floor,
                              width_ref, f"{here}[br{bi}]", note,
                              sub_rows)
                counts.append((f, s, sub_rows))
            f_min = min(c[0] for c in counts)
            s_max = max(c[1] for c in counts)
            # report the fast branch's rows under their own notes,
            # every other branch's as slow-path
            fast_bi = min(range(len(counts)),
                          key=lambda b: counts[b][0])
            for bi, (f, s, sub_rows) in enumerate(counts):
                for r in sub_rows:
                    if bi == fast_bi:
                        rows.append(r)
                    elif r[4] == "collective":
                        # a collective in a not-taken branch is not
                        # fast-path traffic, but must not masquerade
                        # as a slow-path MEMORY op either
                        rows.append((r[0], r[1], r[2], r[3],
                                     "collective-slow"))
                    else:
                        rows.append((r[0], r[1], r[2], r[3],
                                     "slow-branch"))
            fast += f_min
            static += s_max
        elif name == "while":
            for key in ("cond_jaxpr", "body_jaxpr"):
                sub = eqn.params[key].jaxpr
                sub_rows = []
                f, s = _count(sub, threshold, compact_floor,
                              width_ref, f"{here}[{key}]", "loop-body",
                              sub_rows)
                rows.extend(sub_rows)
                # fast path: 0 trips (the kernel's fixpoint loops);
                # static: one trip
                static += s if key == "body_jaxpr" else 0
        elif name == "scan":
            sub = eqn.params["jaxpr"].jaxpr
            sub_rows: List[Tuple[str, str, int, float, str]] = []
            f, s = _count(sub, threshold, compact_floor, width_ref,
                          f"{here}[body]", "scan-body", sub_rows)
            length = int(eqn.params.get("length", 1))
            # the body executes ``length`` times: bill its rows' cost
            # accordingly (modeled_ms_fast counts scan-body rows — a
            # scan-wrapped M-wide pass must not report as free)
            rows.extend((r[0], r[1], r[2], round(r[3] * length, 1),
                         r[4]) for r in sub_rows)
            fast += f * length
            static += s * length
        elif name == "shard_map":
            # v3: descend into the per-shard program — shapes inside
            # are the LOCAL block shapes, so billed widths here are the
            # per-device widths the ops-axis budget gate pins
            # (parallel/opsaxis.py; [shard] tags the rows)
            sub = eqn.params["jaxpr"]
            sub = getattr(sub, "jaxpr", sub)
            f, s = _count(sub, threshold, compact_floor, width_ref,
                          f"{here}[shard]", note, rows)
            fast += f
            static += s
        elif name in _CALLS or "call" in name and "pallas" not in name:
            for sub in _sub_jaxprs(eqn.params):
                f, s = _count(sub, threshold, compact_floor,
                              width_ref, f"{here}", note, rows)
                fast += f
                static += s
        elif name in _COLLECTIVES_P:
            nbytes = sum(
                int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                if getattr(v.aval, "shape", None) is not None else 0
                for v in eqn.outvars)
            rows.append((here, name, nbytes, 0.0,
                         "collective" if note in ("", "fast")
                         else f"collective-{note}"))
        else:
            w = _width(eqn)
            counted = (name == "gather" or name in _SCATTERS or
                       name == "sort" or name in _SCANS or
                       name == "pallas_call")
            if counted and w >= threshold:
                cost = MODELED_MS_PER_OP * max(
                    1.0, _cost_width(eqn, w) / max(width_ref, 1))
                rows.append((here, name, w, round(cost, 1),
                             note or "fast"))
                fast += 1
                static += 1
            elif counted and w >= compact_floor and not note:
                # compacted stage on the fast path: not in the budget
                # count, but priced into compact_risk_ms (conservative
                # fixed cost — the open fixed-vs-linear model cell)
                rows.append((here, name, w, MODELED_MS_PER_OP,
                             "compact"))
    return fast, static


def count_mwide(fn, *args, threshold: Optional[int] = None,
                compact_floor: Optional[int] = None,
                **jaxpr_kwargs) -> ChainAudit:
    """Audit ``fn(*args)``'s trace.  ``args`` may be arrays or
    ``jax.ShapeDtypeStruct``s (tracing is shape-only — auditing the 1M
    production trace costs milliseconds, no device work).

    ``threshold``: minimum random-access width to bill as M-wide;
    default = 1/4 of the widest leading axis among the array arguments.
    ``compact_floor``: minimum width for the compact-stage risk bucket;
    default threshold // 16."""
    closed = jax.make_jaxpr(fn, **jaxpr_kwargs)(*args)
    widest = 1
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", ())
        if shape:
            widest = max(widest, int(shape[0]))
    if threshold is None:
        threshold = max(widest // 4, 1)
    if compact_floor is None:
        compact_floor = max(threshold // 16, 1)
    rows: List[Tuple[str, str, int, float, str]] = []
    fast, static = _count(closed.jaxpr, threshold, compact_floor,
                          widest, "", "", rows)
    order = {"fast": 0, "compact": 1}
    rows.sort(key=lambda r: (order.get(r[4], 2), -r[2]))
    return ChainAudit(fast_path=fast, static=static,
                      threshold=threshold, rows=rows,
                      width_ref=widest, compact_floor=compact_floor)


def audit_materialize(ops: Dict[str, np.ndarray], hints: str,
                      no_deletes: bool,
                      threshold: Optional[int] = None,
                      use_pallas: Optional[bool] = False) -> ChainAudit:
    """Audit the merge kernel's trace for an op-column dict (shape-only;
    the arrays are never touched).  ``use_pallas=True`` audits the
    DEVICE production trace (pallas superops with their in-trace lax
    fallback conds — what runs on TPU); ``use_pallas=False`` audits the
    lax/CPU trace (what the CPU fallback bench runs)."""
    import functools

    from ..ops import merge as merge_mod

    shapes = {k: jax.ShapeDtypeStruct(np.asarray(v).shape,
                                      np.asarray(v).dtype)
              for k, v in ops.items()}
    fn = functools.partial(merge_mod._materialize.__wrapped__,
                           use_pallas=use_pallas, hints=hints,
                           no_deletes=no_deletes)
    audit = count_mwide(fn, shapes, threshold=threshold)
    audit.crowding_leg = "hinted" if merge_mod.crowding_hinted(
        ops, hints, no_deletes) else "counted"
    return audit


def audit_summary(ops: Dict[str, np.ndarray], hints: str,
                  no_deletes: bool) -> dict:
    """Shape-only device-trace audit → the bench stats record."""
    return audit_materialize(ops, hints, no_deletes,
                             use_pallas=True).summary()


def audit_packed_summary(p) -> dict:
    """Shape-only audit of one serving batch (a ``PackedOps``) — the
    flight recorder's sampled production tripwire (obs/flight.py):
    every Nth commit re-derives the kernel trace for the batch that
    just committed and bills it against the CI budget; an ``ok: false``
    summary triggers a JSONL dump, so a trace regression shows up in
    live serving, not just at the next bench round.  Mirrors the hint
    mode the engine itself would elect (``engine._mode``: cond-free
    exhaustive for vouched ingest, verified auto otherwise)."""
    arrays = p.arrays()
    no_deletes = not bool(np.any(np.asarray(arrays["kind"])[:p.num_ops]
                                 == 1))
    hints = "exhaustive" if p.hints_vouched else "auto"
    return audit_materialize(arrays, hints, no_deletes,
                             use_pallas=True).summary()


def _main(argv) -> None:  # pragma: no cover - CLI convenience
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    jax.config.update("jax_enable_x64", True)
    from ..bench import workloads
    ids = [int(a) for a in argv] or [5]
    for cid in ids:
        name, gen = workloads.CONFIGS[cid]
        raw = gen()
        if not isinstance(raw, dict):
            from ..codec import packed as packed_mod
            raw = packed_mod.pack(raw).arrays()
        no_del = not bool(np.any(raw["kind"] == 1))
        for up, tag in ((True, "device/pallas"), (False, "lax/cpu")):
            audit = audit_materialize(raw, "exhaustive", no_del,
                                      use_pallas=up)
            print(f"== config {cid} ({name}) {tag}: modeled "
                  f"{audit.modeled_ms_fast:.0f} ms on-chip ==")
            print(audit.table())


if __name__ == "__main__":  # pragma: no cover
    import sys
    _main(sys.argv[1:])
