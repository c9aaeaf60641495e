"""The chain-length budget, CI-pinned (ISSUE 2 tentpole c; ISSUE 3
lowered it 16 → ≤10 with a width-weighted cost model).

The on-chip cost model (docs/TPU_PROFILE.md §3-4, §7): every M-wide
memory op costs ~6 ms at 1M on v5e (T = 2M-wide passes bill double
under the round-7 width weighting), so <100 ms needs the production
trace's chain ≤ ~10 such ops with modeled ms ≤ 70.
utils/chainaudit.py counts and prices them at TRACE time; this suite
turns the budget into a regression gate — any future kernel change
that re-adds an M-wide pass to the config-5 production trace fails
tier-1 instead of surfacing in the next chip run's profile.

Two traces are pinned: the DEVICE trace (use_pallas=True — the pallas
superops with their in-trace fallback conds, what runs on TPU) at
``FAST_PATH_BUDGET``, and the lax/CPU trace (what the CPU fallback
bench runs) at ``FAST_PATH_BUDGET_LAX``.
"""
import numpy as np
import pytest

import jax

jax.config.update("jax_enable_x64", True)

from crdt_graph_tpu.bench import workloads  # noqa: E402
from crdt_graph_tpu.utils import chainaudit  # noqa: E402

BUDGET = chainaudit.FAST_PATH_BUDGET            # device trace, ≤10
BUDGET_LAX = chainaudit.FAST_PATH_BUDGET_LAX    # lax/CPU trace
MODELED_MS_CAP = chainaudit.MODELED_MS_CAP      # width-weighted, ≤70


def _audit(arrs, hints="exhaustive", use_pallas=False):
    no_del = not bool(np.any(arrs["kind"] == 1))
    return chainaudit.audit_materialize(arrs, hints, no_del,
                                        use_pallas=use_pallas)


def test_config5_production_trace_within_budget():
    """The headline DEVICE trace (1M ops, exhaustive, no deletes,
    slot hints attached, pallas superops with their in-trace
    fallbacks) must fit the CI budget, in count AND in
    width-weighted modeled ms."""
    arrs = workloads.chain_workload(64, 1_000_000)
    audit = _audit(arrs, use_pallas=True)
    assert audit.fast_path <= BUDGET, "\n" + audit.table()
    assert audit.modeled_ms_fast <= MODELED_MS_CAP, "\n" + audit.table()
    assert audit.summary()["ok"]


def test_config5_lax_trace_within_budget():
    """The lax/CPU fallback trace (what the round-end CPU bench runs)
    keeps the sibling machinery and split scans the pallas kernels
    fuse — its own, slightly higher, budget is pinned so CPU-visible
    regressions fail here too."""
    arrs = workloads.chain_workload(64, 1_000_000)
    audit = _audit(arrs, use_pallas=False)
    assert audit.fast_path <= BUDGET_LAX, "\n" + audit.table()


@pytest.mark.parametrize("cid", [6, 7, 8])
def test_adversarial_shapes_share_the_fast_path_budget(cid):
    """The adversarial generators are still causal logs: their FAST
    path must match the budget too (their extra cost lives in the cond
    fallbacks and loop trips the auditor prices as ``static``)."""
    _, gen = workloads.CONFIGS[cid]
    audit = _audit(gen(), use_pallas=True)
    assert audit.fast_path <= BUDGET, f"config {cid}\n" + audit.table()
    assert audit.static >= audit.fast_path


def test_slot_hints_are_load_bearing():
    """Dropping the derived slot-hint columns must re-add the
    resolution gathers (the trace falls back to the gather-based
    exhaustive path) — pinning that the host-side derivation is what
    removed them."""
    arrs = dict(workloads.chain_workload(8, 65_536))
    fused = _audit(arrs)
    from crdt_graph_tpu.codec.packed import SLOT_HINT_COLS
    for k in SLOT_HINT_COLS:
        arrs.pop(k)
    unfused = _audit(arrs)
    assert unfused.fast_path > fused.fast_path


def test_opsaxis_shard_width_budget_config5_1M():
    """ISSUE 13 CI gate: the ops-axis sharded trace at the 1M config-5
    headline bills NO fast-path memory op wider than ceil(M/k) + HALO
    per shard, and its collective traffic stays within the documented
    bound — a regression that silently widens a shard (or re-adds an
    M-wide pass inside the body) fails tier-1 the way the 9-op chain
    budget does."""
    from crdt_graph_tpu.parallel import opsaxis
    if len(__import__("jax").devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    arrs = workloads.chain_workload(64, 1_000_000)
    st = opsaxis.audit_opsaxis(arrs)
    m = 1_000_000 + 2
    assert st["devices"] == 8
    assert st["shard_budget"] == -(-m // 8) + opsaxis.HALO
    assert st["shard_width"] <= st["shard_budget"], st
    assert st["ok"]
    assert 0 < st["collective_bytes"] <= \
        opsaxis.COLLECTIVE_BYTES_CAP_1M, st
    # the production config-5 batch is host-verified all-valid causal,
    # so the crowding pre-pass leg must be the hinted one
    assert st["leg"] == "hinted"


def test_crowding_hints_are_load_bearing():
    """Dropping the crowd columns must re-add the scatter-add + gather
    + cumsum trio to the lax trace — pinning that the host pre-pass is
    what removed it — and the audit summary must record which leg
    compiled."""
    arrs = dict(workloads.chain_workload(8, 65_536))
    assert "crowd_slot" in arrs
    hinted = _audit(arrs)
    assert hinted.crowding_leg == "hinted"
    assert hinted.summary()["crowding_leg"] == "hinted"
    stripped = {k: v for k, v in arrs.items()
                if k not in ("crowd_slot", "crowd_cpos")}
    counted = _audit(stripped)
    assert counted.crowding_leg == "counted"
    # exactly the trio returns
    assert counted.fast_path == hinted.fast_path + 3, (
        f"hinted\n{hinted.table()}\n\ncounted\n{counted.table()}")
    assert counted.summary()["crowding_leg"] == "counted"


def test_counter_basics():
    """The counter itself: gathers/scatters/sorts/scans count at or
    above threshold; elementwise chains, reductions and slices do not;
    cond takes the cheapest branch on the fast path; width-weighted
    costs scale with width above the reference."""
    import jax.numpy as jnp
    from jax import lax

    n = 1024
    x = jax.ShapeDtypeStruct((n,), np.int32)
    i = jax.ShapeDtypeStruct((n,), np.int32)

    def memops(a, idx):
        g = a[jnp.clip(idx, 0, n - 1)]
        s = jnp.zeros_like(a).at[jnp.clip(idx, 0, n - 1)].add(g)
        return lax.cumsum(s) + lax.sort(a)

    audit = chainaudit.count_mwide(memops, x, i, threshold=n)
    assert audit.fast_path == 4, audit.table()
    # all four ops run at the reference width: modeled = 4 x 6 ms
    assert audit.modeled_ms_fast == pytest.approx(
        4 * chainaudit.MODELED_MS_PER_OP)

    def cheap(a, idx):
        for _ in range(5):
            a = (a * 3) ^ (a + 1)
        return jnp.sum(a) + a[:16].sum() + jnp.max(idx)

    assert chainaudit.count_mwide(cheap, x, i,
                                  threshold=n).fast_path == 0

    def with_cond(a, idx):
        return lax.cond(jnp.sum(a) > 0,
                        lambda _: a[jnp.clip(idx, 0, n - 1)] +
                        lax.cumsum(a),
                        lambda _: a * 2, None)

    audit = chainaudit.count_mwide(with_cond, x, i, threshold=n)
    assert audit.fast_path == 0, audit.table()   # cheap branch
    assert audit.static == 2                      # expensive branch

    def wide(a, idx):
        # a 2n-wide scan must bill 2x the per-op cost
        return lax.cumsum(jnp.concatenate([a, a]))

    audit = chainaudit.count_mwide(wide, x, i, threshold=n)
    assert audit.modeled_ms_fast == pytest.approx(
        2 * chainaudit.MODELED_MS_PER_OP)


def test_compact_risk_bucket():
    """Sub-threshold compacted ops land in the disclosed conservative
    fixed-cost bucket, not the budget count."""
    import jax.numpy as jnp

    n = 4096
    x = jax.ShapeDtypeStruct((n,), np.int32)

    def compacted(a):
        small = a[:n // 8]
        return jnp.sum(small[jnp.clip(small, 0, n // 8 - 1)]) + a[0]

    audit = chainaudit.count_mwide(compacted, x, threshold=n)
    assert audit.fast_path == 0
    assert audit.compact_fast == 1
    assert audit.compact_risk_ms == pytest.approx(
        chainaudit.MODELED_MS_PER_OP)
