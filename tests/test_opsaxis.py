"""Ops-axis sharded merge (parallel/opsaxis.py, ISSUE 13): bit-identity
vs the single-device kernel across the sweep shapes, a ≥256k mixed
batch in every hints mode, hostile and missing hints, the mesh-size
edge cases (1-device no-op, non-divisible padded tail, halo-straddling
fallback), and the serving path with the GRAFT_OPSAXIS route on/off
(fingerprints + byte-identical sync windows + unchanged
last_applied_mask attribution)."""
import os

import numpy as np
import pytest

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from crdt_graph_tpu.bench import workloads  # noqa: E402
from crdt_graph_tpu.codec import packed  # noqa: E402
from crdt_graph_tpu.ops import merge, tour_scan  # noqa: E402
from crdt_graph_tpu.parallel import opsaxis  # noqa: E402

FIELDS = ("ts", "parent", "depth", "value_ref", "paths", "exists",
          "tombstone", "dead", "visible", "doc_index", "order",
          "visible_order", "num_nodes", "num_visible", "status")

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")


def assert_identical(arrs, hints="exhaustive", k=8):
    """Pad once, run the stock kernel and the sharded path on the SAME
    padded arrays, compare every table field bitwise."""
    n = arrs["kind"].shape[0]
    n_pad = -(-n // k) * k
    padded = packed.pad_arrays(arrs, n_pad) if n_pad != n else arrs
    want = merge.materialize(padded, hints=hints)
    got = opsaxis.materialize(arrs, k=k, hints=hints)
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(want, f)), np.asarray(getattr(got, f)),
            f)
    return got


# -- the 8 sweep shapes (CONFIGS 1-8, reduced sizes: the generators'
#    structure is size-independent; the 1M-scale budget gate lives in
#    test_chain_audit.py) ---------------------------------------------------

def test_sweep_configs_1_to_4_bit_identical():
    for name, ops in (
            ("editor", workloads.editor_replay(1000)),
            ("tworep", workloads.two_replica_interleaved(2000)),
            ("nested", workloads.nested_tree(6000)),
            ("tombstone", workloads.tombstone_heavy(4000))):
        arrs = packed.pack(ops).arrays()
        hints = "exhaustive" if not np.any(
            arrs["kind"] == packed.KIND_DELETE) else "auto"
        assert_identical(arrs, hints=hints)


def test_sweep_configs_5_to_8_bit_identical():
    for name, arrs in (
            ("chain", workloads.chain_workload(16, 8192)),
            ("descending", workloads.descending_chains(128, 8192)),
            ("comb", workloads.comb_pairs(8192)),
            ("deep", workloads.deep_paths(16, 8192, max_depth=16))):
        assert_identical(arrs)


def test_mixed_deletes_bit_identical():
    arrs = workloads.chain_with_deletes(8192, 8)
    assert (arrs["kind"] == packed.KIND_DELETE).sum() > 500
    assert_identical(arrs, hints="auto")


@pytest.mark.parametrize("shape", ["chain_deletes", "nested"])
def test_visible_values_from_sharded_table_equal_one_device(shape,
                                                            monkeypatch):
    """A kernel apply routed over the ops axis parks the sharded table,
    and the published visible sequence read straight from it (no host
    mirror) equals the one-device table's, its host copy's and the host
    mirror's walk: tombstones included."""
    from crdt_graph_tpu import engine
    from crdt_graph_tpu.core.operation import Add
    ops = (workloads.unpack_ops(workloads.chain_with_deletes(8192, 8))
           if shape == "chain_deletes"
           else workloads.nested_tree(6000, n_replicas=4))
    monkeypatch.setenv("GRAFT_OPSAXIS_MIN_OPS", "1024")
    out = {}
    for route in ("1", "0"):
        monkeypatch.setenv("GRAFT_OPSAXIS", route)
        merges0 = opsaxis.stats()["merges"]
        t = engine.init(7)
        t.apply_packed(packed.pack(ops))
        assert opsaxis.stats()["merges"] - merges0 == int(route)
        c0 = engine.view_counts()["visible_from_table"]
        out[route] = t.visible_values()
        assert engine.view_counts()["visible_from_table"] == c0 + 1
        assert t._mirror is None
        engine.release_published()          # the next merge's first step
        assert isinstance(t._table.status, np.ndarray)
        assert t.visible_values() == out[route]     # the host copy
        t._ensure_mirror()
        assert t.visible_values() == out[route]     # the mirror's walk
    assert out["1"] == out["0"]
    n_adds = sum(isinstance(op, Add) for op in ops)
    n_deletes = len(ops) - n_adds
    assert len(out["1"]) == n_adds - n_deletes


def test_chain_closed_form_through_opsaxis():
    """Not just self-consistency: the sharded result matches the
    closed-form expected visible sequence."""
    arrs = workloads.chain_workload(16, 8192)
    got = assert_identical(arrs)
    want_seq = workloads.chain_expected_ts(16, 8192)
    seq = np.asarray(got.ts)[np.asarray(got.visible_order)][
        :int(got.num_visible)]
    np.testing.assert_array_equal(seq, want_seq)


@pytest.mark.parametrize("hints", ["exhaustive", "auto", "join"])
def test_large_mixed_batch_identity(hints):
    """≥256k ops with deletes over the 8-wide ops axis, in every hints
    mode: the scale bar for a genuinely partitioned merge."""
    arrs = workloads.chain_with_deletes(229_376, 8)
    assert arrs["kind"].shape[0] >= 256_000
    assert (arrs["kind"] == packed.KIND_DELETE).sum() > 10_000
    assert_identical(arrs, hints=hints)


def test_adversarial_shapes_identity_auto():
    """The adversarial generators (descending chains, comb pairs, deep
    paths) at 16k ops in the verified auto mode: worst-case sibling
    contention and fragmentation through the sharded body."""
    for arrs in (workloads.chain_workload(64, 16_384),
                 workloads.descending_chains(256, 16_384),
                 workloads.comb_pairs(16_384),
                 workloads.deep_paths(64, 16_384, max_depth=16)):
        assert_identical(arrs, hints="auto")


def test_chain_closed_form_64_chains_auto():
    """The 64-chain interleave at 64k ops in auto mode matches its
    closed-form visible sequence."""
    arrs = workloads.chain_workload(64, 65_536)
    got = assert_identical(arrs, hints="auto")
    want_seq = workloads.chain_expected_ts(64, 65_536)
    seq = np.asarray(got.ts)[np.asarray(got.visible_order)][
        :int(got.num_visible)]
    np.testing.assert_array_equal(seq, want_seq)


def test_exhaustive_mode_identity_random_session():
    """Vouched (pack-produced) hints of a random multi-replica session
    with deletes through the cond-free exhaustive mode."""
    from test_merge_kernel import _random_session
    _, ops = _random_session(97, n_replicas=4, steps=400)
    p = packed.pack(ops)
    assert p.hints_vouched
    assert_identical(p.arrays(), hints="exhaustive")


def test_hostile_hints_fall_back_identically():
    """Corrupted ranks and links fail the replicated verification; the
    batch takes the sorted+join fallback and still matches the stock
    kernel byte for byte."""
    from test_merge_kernel import _random_session
    _, ops = _random_session(98, n_replicas=3, steps=300)
    arrs = dict(packed.pack(ops).arrays())
    rng = np.random.default_rng(3)
    r = arrs["ts_rank"].copy()
    adds = np.nonzero(r >= 0)[0]
    r[adds] = rng.permutation(r[adds])
    arrs["ts_rank"] = r
    bad = arrs["anchor_pos"].copy()
    bad[bad >= 0] = 0
    arrs["anchor_pos"] = bad
    assert_identical(arrs, hints="auto")


def test_missing_hint_columns_take_the_sorted_path():
    """A batch without ranks, or without any hint column, resolves
    through the sort (and join) inside the sharded body, identical to
    the stock kernel; an unknown hints mode is rejected."""
    from test_merge_kernel import _random_session
    _, ops = _random_session(99, n_replicas=3, steps=200)
    arrs = packed.pack(ops).arrays()
    no_rank = {k: v for k, v in arrs.items() if k != "ts_rank"}
    assert_identical(no_rank, hints="auto")
    bare = {k: v for k, v in arrs.items()
            if k in ("kind", "ts", "parent_ts", "anchor_ts", "depth",
                     "paths", "value_ref", "pos")}
    assert_identical(bare, hints="exhaustive")
    with pytest.raises(ValueError, match="unknown hints mode"):
        opsaxis.materialize(arrs, k=8, hints="trusted")


# -- mesh-size edge cases --------------------------------------------------

def test_one_device_mesh_is_noop_identical():
    """k=1: the sharded path degenerates to the stock kernel (windows
    cover everything, carries are identities, all-gathers are the
    identity) — pinned bit-identical."""
    arrs = workloads.chain_workload(8, 4096)
    assert_identical(arrs, k=1)


def test_non_divisible_ops_pad_tail_shard():
    """An op count the mesh width does not divide pads to the next
    multiple (the tail shard carries the padding) — identical to the
    stock kernel on the same padded arrays."""
    arrs = workloads.chain_workload(3, 3 * 667)     # 2001 rows
    assert arrs["kind"].shape[0] % 8 != 0
    assert_identical(arrs)


def test_shard_edge_straddling_span_takes_halo_fallback():
    """deep_paths parents all resolve to one skeleton slot, so every
    shard but the first sees parent rows far outside its halo window —
    the replicated window check fails and the plane sweep falls back
    to the single-device gather, bit-identically."""
    arrs = workloads.deep_paths(16, 4096, max_depth=16)
    # the straddle really exists: parent slots concentrate on the
    # skeleton while the windowed check only accepts near-diagonal
    # rows (or ROOT/NULL) — shard 7's window cannot contain slot ~15
    w = -(-(4096 + 2) // 8)
    assert 15 < 7 * w - opsaxis.HALO
    assert_identical(arrs)


def test_windowed_plane_rows_unit_fallback():
    """OpsAxisPart.plane_rows directly: a near-diagonal index takes
    the windowed leg, a straddling index the fallback — both
    bit-identical to ``plane[idx]``."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:8]), (opsaxis.AXIS,))
    r = 4096
    plane = jnp.arange(r * 3, dtype=jnp.int64).reshape(r, 3)
    near = jnp.clip(jnp.arange(r, dtype=jnp.int32) - 1, 0, r - 1)
    rng = np.random.default_rng(7)
    far = jnp.asarray(rng.integers(0, r, r).astype(np.int32))

    def body(_):
        part = opsaxis.OpsAxisPart(8)
        return (part.plane_rows(plane, near),
                part.plane_rows(plane, far))

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(opsaxis.AXIS),),
        out_specs=(P(), P()), check_vma=False)
    g_near, g_far = jax.jit(fn)(jnp.zeros(8, jnp.int32))
    np.testing.assert_array_equal(np.asarray(g_near),
                                  np.asarray(plane[near]))
    np.testing.assert_array_equal(np.asarray(g_far),
                                  np.asarray(plane[far]))


def test_sharded_prefix_sums_bit_identical():
    """The ring-carry scan core, across chunk-alignment edge cases."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:8]), (opsaxis.AXIS,))
    rng = np.random.default_rng(0)
    for m in (16, 100, 1000, 4097):
        t = 2 * m
        b = rng.integers(0, 2, t).astype(np.int32)
        w = rng.integers(0, 2, (2, m)).astype(np.int32)

        def body(_):
            return tour_scan.sharded_prefix_sums(
                jnp.asarray(b), jnp.asarray(w), axis=opsaxis.AXIS, k=8)

        fn = jax.shard_map(
            body, mesh=mesh, in_specs=(P(opsaxis.AXIS),),
            out_specs=(P(), P()), check_vma=False)
        ob, ow = jax.jit(fn)(jnp.zeros(8, jnp.int32))
        np.testing.assert_array_equal(np.asarray(ob), np.cumsum(b))
        np.testing.assert_array_equal(np.asarray(ow),
                                      np.cumsum(w, axis=1))


# -- crowding pre-pass hints (ISSUE 13 satellite) --------------------------

@pytest.mark.parametrize("k", [None, 8], ids=["stock", "opsaxis8"])
def test_crowding_hinted_leg_bit_identical_to_counted(k, monkeypatch):
    """The host-derived crowd columns must reproduce the device
    counting trio exactly — both legs pinned on a crowded shape (16
    chain heads under the root) and a contested interleave, in the
    stock kernel and in the sharded body (where the counted leg is the
    ``frame_add`` psum + ring cumsum).  Dropping the crowd columns
    forces the counted leg.  GRAFT_S_CAP is forced below M so the
    compacted sibling branch — the ONLY place the crowd columns are
    live (merge.crowding_hinted gate) — actually compiles at these
    test sizes (at the default 64k cap both legs would trace
    identically and the comparison would be vacuous); the cap is read
    at trace time, so the caches are cleared around it."""
    def run(arrs):
        if k is None:
            return merge.materialize(arrs, hints="exhaustive")
        return opsaxis.materialize(arrs, k=k, hints="exhaustive")

    monkeypatch.setenv("GRAFT_S_CAP", "512")
    for arrs in (workloads.chain_workload(16, 8192),
                 packed.pack(
                     workloads.two_replica_interleaved(2000)).arrays()):
        assert "crowd_slot" in arrs
        no_del = not np.any(arrs["kind"] == packed.KIND_DELETE)
        counted = {c: v for c, v in arrs.items()
                   if c not in ("crowd_slot", "crowd_cpos")}
        try:
            jax.clear_caches()
            assert merge.crowding_hinted(arrs, "exhaustive", no_del)
            assert not merge.crowding_hinted(counted, "exhaustive",
                                             no_del)
            want = run(arrs)
            base = run(counted)
        finally:
            jax.clear_caches()
        for f in FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(want, f)),
                np.asarray(getattr(base, f)), f)


def test_crowding_hints_not_emitted_for_deletes_or_non_causal():
    """Verification, not trust: deletes and non-causal anchors must
    suppress the columns (the counting leg keeps running there)."""
    mixed = workloads.chain_with_deletes(2048, 8)
    assert "crowd_slot" not in mixed
    desc = workloads.descending_chains(64, 2048)
    # descending chains anchor at LARGER timestamps — not causal
    assert "crowd_slot" not in desc


# -- serving path (GRAFT_OPSAXIS on/off) -----------------------------------

def _serve_leg(tmp_path, tag, opsaxis_on, monkeypatch):
    from crdt_graph_tpu.codec import json_codec
    from crdt_graph_tpu.core.operation import Add, Batch
    from crdt_graph_tpu.obs import flight as flight_mod
    from crdt_graph_tpu.serve import ServingEngine
    monkeypatch.setenv("GRAFT_OPSAXIS", "1" if opsaxis_on else "0")
    monkeypatch.setenv("GRAFT_OPSAXIS_MIN_OPS", "1")
    before = opsaxis.stats()["merges"]
    eng = ServingEngine(durable_dir=str(tmp_path / tag),
                        wal_sync="batch", oplog_hot_ops=512,
                        flight=flight_mod.FlightRecorder())
    off = 2 ** 32
    prev = 0
    masks = []
    for start in (1, 1501):
        ops = []
        for c in range(start, start + 1500):
            ops.append(Add(off + c, (prev,), f"v{c}"))
            prev = off + c
        ok, _ = eng.submit("doc", json_codec.dumps(Batch(tuple(ops))))
        assert ok
        masks.append(eng.get("doc").tree.last_applied_mask.copy())
    assert eng.flush(60)
    doc = eng.get("doc")
    sv = doc.snapshot_view()
    windows = {s: sv.ops_since_bytes(s)
               for s in (0, off + 1, off + 1400, off + 2900)}
    routed = opsaxis.stats()["merges"] - before
    out = {"fp": sv.fingerprint(), "sfp": sv.state_fingerprint(),
           "seq": sv.seq, "log_length": sv.log_length,
           "masks": masks, "windows": windows, "routed": routed}
    eng.close()
    return out


def test_serving_fingerprints_and_windows_flag_on_off(tmp_path,
                                                      monkeypatch):
    """The acceptance contract: the same write sequence through the
    routed and unrouted engines publishes bit-identical fingerprints,
    byte-identical sync windows, and the same per-op applied-mask
    attribution — and the on-leg really routed through the sharded
    kernel."""
    on = _serve_leg(tmp_path, "on", True, monkeypatch)
    off = _serve_leg(tmp_path, "off", False, monkeypatch)
    assert on["fp"] == off["fp"]
    assert on["sfp"] == off["sfp"]
    assert on["seq"] == off["seq"]
    assert on["log_length"] == off["log_length"]
    for (a, b) in zip(on["masks"], off["masks"]):
        np.testing.assert_array_equal(a, b)
    assert on["windows"] == off["windows"]
    assert on["routed"] >= 1
    assert off["routed"] == 0


def test_route_gates(monkeypatch):
    monkeypatch.setenv("GRAFT_OPSAXIS", "0")
    assert not opsaxis.enabled_for(1 << 20)
    monkeypatch.setenv("GRAFT_OPSAXIS", "1")
    monkeypatch.setenv("GRAFT_OPSAXIS_MIN_OPS", "1024")
    assert not opsaxis.enabled_for(512)          # below threshold
    assert not opsaxis.enabled_for(1025)         # not divisible
    assert opsaxis.enabled_for(2048)
    k = opsaxis.mesh_devices()
    assert k >= 2 and 2048 % k == 0


def test_prom_families_strict_parse(monkeypatch):
    """crdt_opsaxis_* families ride the unified scrape and survive the
    strict parser."""
    from crdt_graph_tpu.obs import flight as flight_mod
    from crdt_graph_tpu.obs import prom as prom_mod
    from crdt_graph_tpu.serve import ServingEngine
    eng = ServingEngine(flight=flight_mod.FlightRecorder())
    try:
        fams = prom_mod.parse_text(eng.render_prom())
        for fam in ("crdt_opsaxis_enabled", "crdt_opsaxis_devices",
                    "crdt_opsaxis_min_ops", "crdt_opsaxis_halo_rows",
                    "crdt_opsaxis_merges_total",
                    "crdt_opsaxis_routed_ops_total"):
            assert fam in fams, fam
        sm = eng.scheduler_metrics()
        assert "opsaxis" in sm and "devices" in sm["opsaxis"]
    finally:
        eng.close()


@pytest.mark.slow
def test_bench_opsaxis_headline_reduced(tmp_path):
    """The committed-artifact run (BENCH_OPSAXIS_r01_cpu.json shape,
    reduced): fingerprint-equal legs, audit gates green, and the
    broken-path tripwire (a hang / wholesale fallback / widened shard
    reads as red; CPU-mesh slowness per se does not — SHARD_TAIL §7)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bench_opsaxis_headline",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "bench_opsaxis_headline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.run(n_ops=65_536, repeats=1,
                  out_path=str(tmp_path / "BENCH_OPSAXIS_test.json"))
    assert out["bit_identical"]
    assert out["opsaxis_audit"]["ok"]
    assert out["opsaxis_audit"]["leg"] == "hinted"
    assert out["tripwire"]["ok"], out["p50_ms"]


# -- the route's counters and span through the serving engine --------------

def _chain_body(off, start, n):
    from crdt_graph_tpu.codec import json_codec
    from crdt_graph_tpu.core.operation import Add, Batch
    ops, prev = [], 0
    for c in range(start, start + n):
        ops.append(Add(off + c, (prev,), f"v{c}"))
        prev = off + c
    return json_codec.dumps(Batch(tuple(ops)))


def test_served_route_counts_rows_and_places(monkeypatch):
    """A push counts its routed merge and its bucket rows in the
    engine's counters, and the routed merge is one ``opsaxis.place``
    span; a push whose candidate set stays under the threshold counts
    its rows in ``kernel_rows`` only."""
    from crdt_graph_tpu.obs import flight as flight_mod
    from crdt_graph_tpu.serve import ServingEngine
    from crdt_graph_tpu.utils import profiling
    monkeypatch.setenv("GRAFT_OPSAXIS", "1")
    monkeypatch.setenv("GRAFT_OPSAXIS_MIN_OPS", "2048")
    eng = ServingEngine(flight=flight_mod.FlightRecorder())
    keys = ("opsaxis_merges", "opsaxis_rows", "kernel_rows")

    def push(doc, n):
        c0 = eng.counters.snapshot()
        p0 = profiling.span_stats("opsaxis.").get(
            "opsaxis.place", {"count": 0})["count"]
        ok, _ = eng.submit(doc, _chain_body(2 ** 32, 1, n))
        assert ok and eng.flush(60)
        c1 = eng.counters.snapshot()
        p1 = profiling.span_stats("opsaxis.")["opsaxis.place"]["count"]
        return tuple(c1[k] - c0.get(k, 0) for k in keys), p1 - p0

    try:
        # one merge, a candidate set of 3072 rows in a 4096 bucket,
        # past the threshold
        assert push("routed", 3072) == ((1, 4096, 4096), 1)
        # one merge of a 1024-row bucket, under the threshold
        assert push("local", 1024) == ((0, 0, 1024), 0)
        assert eng.get("routed").tree.log_length == 3072
    finally:
        eng.close()
