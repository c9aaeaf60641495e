"""Partition & bit-rot chaos, network half (cluster/netchaos.py):
deterministic fault injection on every inter-node link, the peer
health / circuit-breaker degradation layer, the bounded-staleness read
contract, and the forward-deadline budget.

Every chaos test prints its ``NetChaos.describe()`` replay line first,
so a red run's captured stdout carries the exact seed + schedule to
reproduce it verbatim.
"""
import json
import threading
import time
from http.client import HTTPConnection

import pytest

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from crdt_graph_tpu.cluster import (FleetServer, MemoryKV, NetChaos,
                                    NetChaosSpecError)
from crdt_graph_tpu.codec import json_codec
from crdt_graph_tpu.core.operation import Add, Batch


def ts(r, c):
    return r * 2**32 + c


def req(port, method, path, body=None, headers=None, timeout=60):
    conn = HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body, headers=headers or {})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, raw, dict(resp.getheaders())
    finally:
        conn.close()


def _chain(rid, n, start=1, prev=0):
    ops = []
    for c in range(start, start + n):
        ops.append(Add(ts(rid, c), (prev,), f"r{rid}:{c}"))
        prev = ts(rid, c)
    return json_codec.dumps(Batch(tuple(ops)))


def _spawn_fleet(kv, names, **kw):
    """Deterministic fleet: huge TTL, dormant daemon (tests drive
    ``sync_now``)."""
    fleet = {}
    for n in names:
        fleet[n] = FleetServer(n, kv, ttl_s=600.0,
                               ae_interval_s=3600.0, **kw)
    for fs in fleet.values():
        fs.node.refresh_ring()
    return fleet


def _stop_fleet(fleet):
    for fs in fleet.values():
        try:
            fs.stop()
        except Exception:  # noqa: BLE001 — teardown boundary
            pass


def _doc_owned_by(ring, owner, prefix="doc"):
    for i in range(500):
        d = f"{prefix}{i}"
        if ring.primary(d) == owner:
            return d
    pytest.fail(f"no doc routed to {owner}")


def _post_retry(port, doc, body, deadline_s=30):
    """Client write with 429/503/connection retry — chaos on the
    forward path legally sheds; an acked-loss check only counts 200s."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            st, raw, _ = req(port, "POST", f"/docs/{doc}/ops",
                             body=body, timeout=30)
        except OSError:
            time.sleep(0.05)
            continue
        if st == 200 and json.loads(raw).get("accepted"):
            return True
        if st in (429, 503):
            time.sleep(0.05)
            continue
        pytest.fail(f"write -> {st}: {raw[:200]!r}")
    return False


def _sync_all(fleet, docs, deadline_s=60, require=None):
    """Drive sync rounds until the named (or all) nodes agree on every
    doc's replica-independent fingerprint.  Returns the converged
    fingerprints."""
    names = sorted(require or fleet)
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        for n in names:
            fleet[n].node.antientropy.sync_now()
        fps = {}
        ok = True
        for doc in docs:
            seen = set()
            for n in names:
                st, _, hdr = req(fleet[n].port, "GET", f"/docs/{doc}")
                if st != 200:
                    ok = False
                    continue
                seen.add(hdr["X-State-Fingerprint"])
            fps[doc] = seen
            ok = ok and len(seen) == 1
        if ok:
            return {d: next(iter(s)) for d, s in fps.items()}
        time.sleep(0.02)
    pytest.fail(f"no convergence within {deadline_s}s: {fps}")


def _values(fleet_server, doc):
    st, raw, _ = req(fleet_server.port, "GET", f"/docs/{doc}")
    assert st == 200, raw
    return json.loads(raw)["values"]


# -- spec grammar + determinism ----------------------------------------------


def test_spec_parse_roundtrip_and_errors():
    c = NetChaos(7, "drop=0.25;delay=1-5@0.5;throttle=65536;cut=0.1;"
                    "dup=0.2;part=n0|n1+n2@3-9;oneway=a>b@0-4;"
                    "flap=x|y@10/3")
    assert c.drop_p == 0.25
    assert c.delay == (0.001, 0.005, 0.5)
    assert c.throttle_bps == 65536
    assert c.cut_p == 0.1 and c.dup_p == 0.2
    assert len(c.partitions) == 3
    assert c.describe().startswith("GRAFT_NETCHAOS=7:drop=0.25")
    for bad in ("frob=1", "part=a|b", "flap=a|b@0/0", "drop=x",
                "part=|b@0-4"):
        with pytest.raises(NetChaosSpecError):
            NetChaos(1, bad)
    # env parsing (the multi-process soak's entry)
    import os
    from crdt_graph_tpu.cluster import netchaos as nc_mod
    os.environ["GRAFT_NETCHAOS"] = "3:drop=0.5"
    try:
        nc_mod.reset_env_chaos()
        env = nc_mod.env_chaos()
        assert env is not None and env.seed == 3 and env.drop_p == 0.5
    finally:
        del os.environ["GRAFT_NETCHAOS"]
        nc_mod.reset_env_chaos()
    assert NetChaos.from_env() is None


def _fates(chaos, src, dst, n):
    """The link's next n request fates as a refusal bitmask string."""
    out = []
    for _ in range(n):
        try:
            chaos.decide(src, dst)
            out.append(".")
        except ConnectionRefusedError:
            out.append("X")
    return "".join(out)


def test_partition_schedules_on_link_request_index():
    c = NetChaos(0, "part=a|b@2-4")
    assert _fates(c, "a", "b", 6) == "..XX.."      # [2,4) blocked
    assert _fates(c, "b", "a", 6) == "..XX.."      # symmetric
    assert _fates(c, "a", "c", 3) == "..."         # unrelated link
    c = NetChaos(0, "oneway=a>b@0-2")
    assert _fates(c, "a", "b", 3) == "XX."
    assert _fates(c, "b", "a", 3) == "..."         # asymmetric
    c = NetChaos(0, "flap=a|b@4/2")
    assert _fates(c, "a", "b", 8) == "XX..XX.."    # flapping
    c = NetChaos(0, "part=a|*@0-2")
    assert _fates(c, "a", "anything", 3) == "XX."  # wildcard group


def test_seeded_decisions_are_reproducible():
    spec = "drop=0.4;delay=0-1@0.5;cut=0.2;dup=0.3"
    a = _fates(NetChaos(42, spec), "n0", "n1", 64)
    b = _fates(NetChaos(42, spec), "n0", "n1", 64)
    assert a == b and "X" in a and "." in a
    # a different seed gives a different stream; a different link of
    # the SAME plan draws independently
    assert _fates(NetChaos(43, spec), "n0", "n1", 64) != a
    c = NetChaos(42, spec)
    assert _fates(c, "n0", "n1", 64) == a
    assert _fates(c, "n1", "n0", 64) != a


# -- the acceptance matrix: partition/heal × {sym, asym, flapping} -----------


def test_partition_matrix_converges_zero_acked_loss():
    """The seeded partition/heal matrix (ISSUE 14 acceptance):
    symmetric isolation, an asymmetric one-way cut healed around
    transitively, and a flapping link — over a lossy/slow link plan —
    each phase ends in fingerprint-equal convergence with every acked
    value present on every replica.  Reproducible from the printed
    replay line."""
    chaos = NetChaos(1337, "drop=0.1;delay=1-4@0.3")
    print("REPLAY:", chaos.describe())
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1", "n2"), netchaos=chaos,
                         breaker_threshold=50)
    acked = {}                      # doc -> [values]
    try:
        ring = fleet["n0"].node.ring()
        doc_a = _doc_owned_by(ring, "n0", prefix="pm")
        doc_c = _doc_owned_by(ring, "n2", prefix="pm")
        docs = [doc_a, doc_c]

        def write(port, doc, rid, n, start, prev=0):
            assert _post_retry(port, doc, _chain(rid, n, start=start,
                                                 prev=prev))
            acked.setdefault(doc, []).extend(
                f"r{rid}:{c}" for c in range(start, start + n))

        # phase 0: baseline through every node, converge
        write(fleet["n0"].port, doc_a, 10, 4, 1)
        write(fleet["n2"].port, doc_c, 30, 4, 1)
        _sync_all(fleet, docs)

        # phase 1: SYMMETRIC — n2 cut off from both peers
        chaos.block_groups({"n2"}, {"n0", "n1"})
        write(fleet["n0"].port, doc_a, 11, 4, 1)
        # n2 keeps acking writes to ITS doc while isolated (local
        # apply — availability under partition)
        write(fleet["n2"].port, doc_c, 31, 4, 1)
        fps = _sync_all(fleet, [doc_a], require=("n0", "n1"))
        st, _, hdr = req(fleet["n2"].port, "GET", f"/docs/{doc_a}")
        assert hdr["X-State-Fingerprint"] != fps[doc_a], \
            "n2 cannot have n1's state through a full partition"
        assert float(hdr["X-Ae-Lag-Seconds"]) > 0.0
        chaos.heal()
        _sync_all(fleet, docs)

        # phase 2: ASYMMETRIC — n1 cannot pull from n0, but the write
        # still reaches n1 transitively through n2 (pull-based
        # anti-entropy routes around one-way cuts)
        chaos.block("n1", "n0", oneway=True)
        write(fleet["n0"].port, doc_a, 12, 4, 1)
        _sync_all(fleet, [doc_a], deadline_s=90)
        assert "r12:4" in _values(fleet["n1"], doc_a)
        chaos.heal()

        # phase 3: FLAPPING — the n0↔n1 link cuts and heals repeatedly
        # while writes keep landing; convergence after the last heal
        for k in range(4):
            chaos.block("n0", "n1")
            write(fleet["n0"].port, doc_a, 13 + k, 2, 1)
            for n in fleet:
                fleet[n].node.antientropy.sync_now()
            chaos.heal()
            for n in fleet:
                fleet[n].node.antientropy.sync_now()
        _sync_all(fleet, docs)

        # ZERO ACKED LOSS: every value ever acked is on every replica
        for doc in docs:
            for n, fs in fleet.items():
                got = set(_values(fs, doc))
                missing = [v for v in acked[doc] if v not in got]
                assert not missing, \
                    (f"{n} lost acked values {missing[:4]} "
                     f"({chaos.describe()})")
        # the fault plan actually fired (this was not a clean run)
        stats = chaos.stats()["counters"]
        assert stats["partition_blocks"] > 0
        assert stats["drops"] + stats["delays"] > 0
    finally:
        print("REPLAY:", chaos.describe(),
              "counters:", chaos.stats()["counters"])
        _stop_fleet(fleet)


# -- cut / dup faults through the real anti-entropy wire ---------------------


def test_cut_mid_response_is_a_peer_failure_then_heals():
    chaos = NetChaos(5, "cut=1")
    print("REPLAY:", chaos.describe())
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1"), netchaos=chaos)
    try:
        doc = _doc_owned_by(fleet["n0"].node.ring(), "n0")
        assert _post_retry(fleet["n0"].port, doc, _chain(1, 5))
        ae = fleet["n1"].node.antientropy
        # every response dies mid-body: a counted peer failure, never
        # an escaped exception or a half-applied window
        assert ae.sync_now() == {"n0": False}
        st = ae.stats()["peers"]["n0"]
        assert st["failures"] >= 1 and st["health"] < 1.0
        assert chaos.stats()["counters"]["cuts"] >= 1
        chaos.cut_p = 0.0           # the link heals
        assert ae.sync_now() == {"n0": True}
        assert _values(fleet["n1"], doc) == [f"r1:{c}"
                                             for c in range(1, 6)]
    finally:
        _stop_fleet(fleet)


def test_chaos_over_pooled_connections_poisons_and_reopens():
    """ISSUE 15 acceptance: all inter-node traffic now rides the
    per-node connection pool THROUGH netchaos.connect — an injected
    cut poisons exactly the pooled connection it hit (evicted, never
    reused), the next round reopens fresh, and a healthy steady state
    reuses connections across rounds with zero acked loss."""
    chaos = NetChaos(11, "")
    print("REPLAY:", chaos.describe())
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1"), netchaos=chaos)
    try:
        doc = _doc_owned_by(fleet["n0"].node.ring(), "n0")
        assert _post_retry(fleet["n0"].port, doc, _chain(1, 5))
        ae = fleet["n1"].node.antientropy
        pool = fleet["n1"].node.pool

        # clean rounds: round 1 opens, round 2+ REUSE the pooled link
        assert ae.sync_now() == {"n0": True}
        opens_clean = pool.stats()["opens"]
        assert opens_clean >= 1
        assert ae.sync_now() == {"n0": True}
        st = pool.stats()
        assert st["opens"] == opens_clean       # no new connection
        assert st["reuses"] >= 1

        # arm cut=1: the response dies mid-body; the failure poisons
        # the pooled connection (never returned to the idle set)
        chaos.cut_p = 1.0
        assert ae.sync_now() == {"n0": False}
        st = pool.stats()
        assert st["poisoned"] >= 1, st

        # heal: the next round must OPEN a fresh connection (the
        # poisoned one is gone) and fully converge — zero acked loss
        chaos.cut_p = 0.0
        assert ae.sync_now() == {"n0": True}
        st2 = pool.stats()
        assert st2["opens"] > opens_clean, (st, st2)
        assert _values(fleet["n1"], doc) == [f"r1:{c}"
                                             for c in range(1, 6)]
        # partition blocks poison too (a drop fires before bytes move,
        # but the caller cannot know — conservative eviction)
        chaos.block("n1", "n0")
        assert ae.sync_now() == {"n0": False}
        assert pool.stats()["poisoned"] > st2["poisoned"]
        chaos.heal()
        assert ae.sync_now() == {"n0": True}
    finally:
        print("REPLAY:", chaos.describe(),
              "pool:", fleet["n1"].node.pool.stats())
        _stop_fleet(fleet)


def test_dup_reordered_window_deliveries_absorb():
    """dup=1: every pull re-serves the link's previous response — the
    puller applies stale windows and its mark regresses, and the CRDT
    absorbs all of it (idempotence is the contract under reordering)."""
    chaos = NetChaos(9, "dup=1")
    print("REPLAY:", chaos.describe())
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1"), netchaos=chaos)
    try:
        doc = _doc_owned_by(fleet["n0"].node.ring(), "n0")
        assert _post_retry(fleet["n0"].port, doc, _chain(1, 6))
        ae = fleet["n1"].node.antientropy
        for _ in range(6):
            ae.sync_now()
        assert chaos.stats()["counters"]["dups"] >= 1
        assert _values(fleet["n1"], doc) == [f"r1:{c}"
                                             for c in range(1, 7)]
        st, _, h0 = req(fleet["n0"].port, "GET", f"/docs/{doc}")
        st, _, h1 = req(fleet["n1"].port, "GET", f"/docs/{doc}")
        assert h0["X-State-Fingerprint"] == h1["X-State-Fingerprint"]
    finally:
        _stop_fleet(fleet)


# -- peer health, circuit breaker, probe pulls (satellite pins) --------------


def test_backoff_hygiene_first_success_fully_resets():
    """Satellite pin: a peer's fail_streak/backoff_until reset
    completely on the first successful round — no residual penalty."""
    chaos = NetChaos(2, "")
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1"), netchaos=chaos)
    try:
        doc = _doc_owned_by(fleet["n0"].node.ring(), "n0")
        assert _post_retry(fleet["n0"].port, doc, _chain(1, 3))
        ae = fleet["n1"].node.antientropy
        chaos.block("n1", "n0")
        for _ in range(2):
            assert ae.sync_now() == {"n0": False}
        st = ae.stats()["peers"]["n0"]
        assert st["fail_streak"] == 2 and st["backoff_s"] > 0
        assert st["health"] < 1.0
        chaos.heal()
        assert ae.sync_now() == {"n0": True}
        st = ae.stats()["peers"]["n0"]
        assert st["fail_streak"] == 0
        assert st["backoff_s"] == 0.0
        assert not st["breaker_open"]
        h1 = st["health"]
        assert ae.sync_now() == {"n0": True}
        assert ae.stats()["peers"]["n0"]["health"] > h1  # recovering
    finally:
        _stop_fleet(fleet)


def test_breaker_opens_probe_pull_closes():
    """Satellite pin: past the threshold the breaker opens; a priority
    wake then performs EXACTLY ONE probe pull (listing + one window of
    one doc) rather than a full unthrottled round; the probe's success
    closes the breaker and the next round is full again."""
    chaos = NetChaos(4, "")
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1"), netchaos=chaos,
                         breaker_threshold=3)
    try:
        ring = fleet["n0"].node.ring()
        owned = []
        for i in range(500):
            if ring.primary(f"bk{i}") == "n0":
                owned.append(f"bk{i}")
            if len(owned) == 3:
                break
        for k, d in enumerate(owned):
            assert _post_retry(fleet["n0"].port, d, _chain(5 + k, 3))
        ae = fleet["n1"].node.antientropy
        assert ae.sync_now() == {"n0": True}     # marks for all 3 docs
        chaos.block("n1", "n0")
        for _ in range(3):
            assert ae.sync_now() == {"n0": False}
        st = ae.stats()["peers"]["n0"]
        assert st["breaker_open"] and st["breaker_opens"] == 1
        pulls_before = st["pulls"]

        # new writes the probe round must NOT fully pull
        for k, d in enumerate(owned):
            assert _post_retry(fleet["n0"].port, d,
                               _chain(5 + k, 2, start=4,
                                      prev=ts(5 + k, 3)))
        chaos.heal()
        # priority wake while the breaker is open: exactly one probe
        lag_before_probe = ae.lag_seconds()
        ae.request_priority(owned[0])
        assert ae.sync_now(respect_backoff=False) == {"n0": True}
        st = ae.stats()["peers"]["n0"]
        assert st["probes"] == 1
        assert st["pulls"] == pulls_before + 1, \
            "probe must pull exactly ONE window of ONE doc"
        assert not st["breaker_open"]            # success closed it
        assert st["fail_streak"] == 0
        # a probe proves reachability, NOT sync: the lag clock (the
        # bounded-staleness 503 input) must not reset until the next
        # FULL round has actually pulled everything
        assert ae.lag_seconds() >= lag_before_probe
        # the NEXT round is a full sync again: every doc catches up
        assert ae.sync_now() == {"n0": True}
        assert ae.lag_seconds() < lag_before_probe  # genuinely fresh
        st = ae.stats()["peers"]["n0"]
        assert st["pulls"] >= pulls_before + 1 + len(owned)
        for k, d in enumerate(owned):
            assert f"r{5 + k}:5" in _values(fleet["n1"], d)
        assert ae.stats()["probe_pulls"] == 1
    finally:
        _stop_fleet(fleet)


def test_breaker_open_skips_full_rounds_on_backoff():
    """While open (and not priority-woken), rounds respect the capped
    backoff and never run a full sync against the dead peer."""
    chaos = NetChaos(6, "")
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1"), netchaos=chaos,
                         breaker_threshold=2)
    try:
        doc = _doc_owned_by(fleet["n0"].node.ring(), "n0")
        assert _post_retry(fleet["n0"].port, doc, _chain(1, 3))
        ae = fleet["n1"].node.antientropy
        assert ae.sync_now() == {"n0": True}
        chaos.block("n1", "n0")
        for _ in range(2):
            ae.sync_now()
        st = ae.stats()["peers"]["n0"]
        assert st["breaker_open"]
        probes0 = st["probes"]
        # a backoff-respecting round inside the backoff window does
        # NOTHING against the peer — no pull, no probe
        res = ae.sync_now(respect_backoff=True)
        assert "n0" not in res
        assert ae.stats()["peers"]["n0"]["probes"] == probes0
        # a backoff-ignoring round (priority shape) probes, and the
        # probe itself fails against the still-cut link — the failure
        # is counted, the breaker stays open
        res = ae.sync_now(respect_backoff=False)
        assert res == {"n0": False}
        st = ae.stats()["peers"]["n0"]
        assert st["probes"] == probes0 + 1 and st["breaker_open"]
    finally:
        _stop_fleet(fleet)


# -- bounded-staleness reads (tentpole piece 2) ------------------------------


def test_bounded_staleness_read_contract():
    chaos = NetChaos(8, "")
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1"), netchaos=chaos)
    try:
        doc = _doc_owned_by(fleet["n0"].node.ring(), "n0")
        assert _post_retry(fleet["n0"].port, doc, _chain(1, 3))
        ae = fleet["n1"].node.antientropy
        assert ae.sync_now() == {"n0": True}
        # fresh replica: bounded read serves, lag stamped
        st, _, hdr = req(fleet["n1"].port, "GET", f"/docs/{doc}",
                         headers={"X-Max-Staleness": "5"})
        assert st == 200
        assert float(hdr["X-Ae-Lag-Seconds"]) < 5.0
        # partition the replica; its lag grows past a tight bound
        chaos.block("n1", "n0")
        ae.sync_now()
        time.sleep(0.15)
        st, raw, hdr = req(fleet["n1"].port, "GET", f"/docs/{doc}",
                           headers={"X-Max-Staleness": "0.05"})
        assert st == 503, raw
        assert "Retry-After" in hdr
        body = json.loads(raw)
        assert body["ae_lag_s"] > 0.05
        assert float(hdr["X-Ae-Lag-Seconds"]) > 0.05
        # snapshots honor the same bound; unbounded reads still serve
        st, _, _ = req(fleet["n1"].port, "GET", f"/docs/{doc}/snapshot",
                       headers={"X-Max-Staleness": "0.05"})
        assert st == 503
        st, _, _ = req(fleet["n1"].port, "GET", f"/docs/{doc}")
        assert st == 200
        # malformed bounds (bogus/nan/-inf) fall back to the (unset)
        # server default — nan would otherwise 503 forever (lag <= nan
        # is always False) — and +inf is honored as explicitly
        # unbounded; all serve here
        for bad in ("bogus", "nan", "inf", "-inf"):
            st, _, _ = req(fleet["n1"].port, "GET", f"/docs/{doc}",
                           headers={"X-Max-Staleness": bad})
            assert st == 200, bad
        assert fleet["n1"].node.counters["staleness_503"] >= 2
        # heal: one successful round resets the lag; bounded serves
        chaos.heal()
        assert ae.sync_now() == {"n0": True}
        st, _, _ = req(fleet["n1"].port, "GET", f"/docs/{doc}",
                       headers={"X-Max-Staleness": "5"})
        assert st == 200
    finally:
        _stop_fleet(fleet)


def test_server_default_staleness_bound():
    """GRAFT_MAX_STALENESS_S as a server-wide default (here via the
    ctor knob it feeds): unbounded requests inherit it."""
    chaos = NetChaos(12, "")
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1"), netchaos=chaos,
                         max_staleness_s=0.05)
    try:
        doc = _doc_owned_by(fleet["n0"].node.ring(), "n0")
        assert _post_retry(fleet["n0"].port, doc, _chain(1, 3))
        ae = fleet["n1"].node.antientropy
        assert ae.sync_now() == {"n0": True}
        chaos.block("n1", "n0")
        ae.sync_now()
        time.sleep(0.15)
        st, _, _ = req(fleet["n1"].port, "GET", f"/docs/{doc}")
        assert st == 503            # no header needed — server default
        # a LOOSER per-request bound overrides the strict default
        st, _, _ = req(fleet["n1"].port, "GET", f"/docs/{doc}",
                       headers={"X-Max-Staleness": "60"})
        assert st == 200
        # +inf is an EXPLICIT unbounded request — it overrides even a
        # strict server default; nan stays malformed and inherits it
        st, _, _ = req(fleet["n1"].port, "GET", f"/docs/{doc}",
                       headers={"X-Max-Staleness": "inf"})
        assert st == 200
        st, _, _ = req(fleet["n1"].port, "GET", f"/docs/{doc}",
                       headers={"X-Max-Staleness": "nan"})
        assert st == 503
    finally:
        _stop_fleet(fleet)


def test_never_synced_replica_reports_unbounded_lag():
    """A replica that has never completed a full round since daemon
    start cannot bound how stale its (possibly recovered) state is:
    lag is inf — a bounded read refuses, an unbounded read stamps the
    honest ``inf`` — until the first full sync lands.  A start-relative
    near-zero here would be exactly the silent-stale lie the 503
    exists to prevent (a node restarted after an hour of downtime
    would serve hour-old data as fresh)."""
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1"))
    try:
        doc = _doc_owned_by(fleet["n0"].node.ring(), "n0")
        assert _post_retry(fleet["n0"].port, doc, _chain(1, 3))
        assert fleet["n0"].node.ae_lag_seconds() == float("inf")

        def strict_loads(raw):
            # RFC 8259 has no Infinity/NaN literals — the wire must
            # serialize unbounded lag as null, never lean on Python's
            # lenient json.loads
            return json.loads(
                raw, parse_constant=lambda c: pytest.fail(
                    f"non-RFC JSON literal {c!r} on the wire"))

        st, raw, hdr = req(fleet["n0"].port, "GET", f"/docs/{doc}",
                           headers={"X-Max-Staleness": "60"})
        assert st == 503
        assert strict_loads(raw)["ae_lag_s"] is None
        assert hdr["X-Ae-Lag-Seconds"] == "inf"
        st, raw, _ = req(fleet["n0"].port, "GET", "/cluster")
        assert strict_loads(raw)["ae_lag_s"] is None
        st, _, hdr = req(fleet["n0"].port, "GET", f"/docs/{doc}")
        assert st == 200
        assert float(hdr["X-Ae-Lag-Seconds"]) == float("inf")
        # first full round: the bound becomes enforceable and serves
        assert fleet["n0"].node.antientropy.sync_now() == {"n1": True}
        st, _, hdr = req(fleet["n0"].port, "GET", f"/docs/{doc}",
                         headers={"X-Max-Staleness": "60"})
        assert st == 200
        assert float(hdr["X-Ae-Lag-Seconds"]) < 60.0
    finally:
        _stop_fleet(fleet)


# -- forward-deadline budget (satellite) -------------------------------------


def test_forward_budget_caps_handler_pin_time():
    """Satellite pin: an unreachable primary can pin a forwarding
    handler only up to the end-to-end budget, then the client gets an
    honest 503 + Retry-After."""
    chaos = NetChaos(3, "")
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1"), netchaos=chaos,
                         forward_budget_s=0.6, forward_retries=50)
    try:
        doc = _doc_owned_by(fleet["n0"].node.ring(), "n1")
        chaos.block("n0", "n1", oneway=True)   # forward path only
        t0 = time.monotonic()
        st, raw, hdr = req(fleet["n0"].port, "POST",
                           f"/docs/{doc}/ops", body=_chain(1, 3),
                           timeout=30)
        elapsed = time.monotonic() - t0
        assert st == 503, raw
        assert "Retry-After" in hdr
        assert elapsed < 5.0, \
            f"handler pinned {elapsed:.1f}s past the 0.6s budget"
        assert fleet["n0"].node.counters["forward_budget_exhausted"] \
            >= 1
        assert fleet["n0"].node.counters["forwarded_err"] >= 1
        # heal: the same write forwards and acks
        chaos.heal()
        assert _post_retry(fleet["n0"].port, doc, _chain(1, 3))
    finally:
        _stop_fleet(fleet)


# -- oracle-checked chaos load (run_fleet netchaos leg) ----------------------


def test_fleet_loadgen_under_netchaos_zero_violations():
    """The session-guarantee oracle stays clean while the fleet's
    inter-node links run delayed + duplicated/reordered deliveries —
    the acceptance matrix's oracle leg."""
    from crdt_graph_tpu.bench import loadgen
    cfg = loadgen.LoadgenConfig(
        n_servers=3, n_sessions=6, n_docs=2, writes_per_session=4,
        delta_size=6, giant_ops=0, kill_mid_run=False,
        lag_probe_every=2, lease_ttl_s=3.0, ae_interval_s=0.1,
        seed=21, netchaos_spec="delay=1-10@0.5;dup=0.3")
    rep = loadgen.run_fleet(cfg)
    print("REPLAY:", rep["netchaos_replay"])
    assert rep["errors"] == [], (rep["errors"], rep["netchaos_replay"])
    assert rep["violations"] == [], rep["netchaos_replay"]
    assert rep["oracle"]["violations_total"] == 0
    assert len(rep["converged"]) == 2
    nc = rep["netchaos"]["counters"]
    assert nc["delays"] > 0                  # the plan actually fired
    assert nc["requests"] > 0


def test_fleet_loadgen_client_links_under_chaos():
    """netchaos_clients=True runs the SESSION links through the plan
    too (delay-only: duplicated RESPONSES to a client would corrupt
    the oracle's own observation channel, not the server — reordering
    coverage lives on the inter-node links above and in the dup
    anti-entropy test)."""
    from crdt_graph_tpu.bench import loadgen
    cfg = loadgen.LoadgenConfig(
        n_servers=3, n_sessions=6, n_docs=2, writes_per_session=3,
        delta_size=5, giant_ops=0, kill_mid_run=False,
        lag_probe_every=2, lease_ttl_s=3.0, ae_interval_s=0.1,
        seed=23, netchaos_spec="delay=1-8@0.6",
        netchaos_clients=True)
    rep = loadgen.run_fleet(cfg)
    print("REPLAY:", rep["netchaos_replay"])
    assert rep["errors"] == [], (rep["errors"], rep["netchaos_replay"])
    assert rep["violations"] == [], rep["netchaos_replay"]
    # client links really rode the plan (session-named links exist)
    assert rep["netchaos"]["links"] > 2


# -- the slow multi-process soak ---------------------------------------------


def _proc_env(extra=None):
    import os
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "true"
    env.update(extra or {})
    return env


@pytest.mark.slow
def test_fleet_soak_processes_under_netchaos(tmp_path):
    """3 real node processes over a shared FileKV spool, every
    process armed with the SAME GRAFT_NETCHAOS plan (lossy, slow,
    briefly partitioned links): the fleet still converges to
    fingerprint-equal snapshots holding every acked value."""
    import os
    import subprocess
    import sys
    netchaos = "77:drop=0.1;delay=2-20@0.5;part=n2|n0+n1@20-60"
    print("REPLAY: GRAFT_NETCHAOS=" + netchaos)
    spool = str(tmp_path / "fleet-kv")
    procs, ports = {}, {}
    try:
        for n in ("n0", "n1", "n2"):
            procs[n] = subprocess.Popen(
                [sys.executable, "-m", "crdt_graph_tpu.cluster",
                 "--cpu", "--name", n, "--kv-dir", spool,
                 "--port", "0", "--ttl", "2.0",
                 "--ae-interval", "0.2"],
                cwd=os.path.join(os.path.dirname(__file__), ".."),
                env=_proc_env({"GRAFT_NETCHAOS": netchaos}),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True)
            line = procs[n].stdout.readline()
            assert line.startswith("READY "), line
            info = json.loads(line[len("READY "):])
            ports[n] = int(info["addr"].rsplit(":", 1)[1])
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            views = {n: json.loads(req(p, "GET", "/cluster")[1])
                     for n, p in ports.items()}
            if all(len(v["members"]) == 3 for v in views.values()):
                break
            time.sleep(0.1)
        else:
            pytest.fail("fleet membership never stabilized")

        acked = []
        for k in range(8):
            rid = 20 + k
            entry = ports[f"n{k % 3}"]
            assert _post_retry(entry, "soak0", _chain(rid, 40),
                               deadline_s=120)
            acked.extend(f"r{rid}:{c}" for c in range(1, 41))
        # convergence: equal replica-independent fingerprints + every
        # acked value everywhere, THROUGH the lossy plan
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            fps = {}
            for n, p in ports.items():
                try:
                    st, raw, hdr = req(p, "GET", "/docs/soak0")
                except OSError:
                    break
                if st != 200:
                    break
                fps[n] = hdr["X-State-Fingerprint"]
            if len(fps) == 3 and len(set(fps.values())) == 1:
                break
            time.sleep(0.5)
        assert len(set(fps.values())) == 1, (fps, netchaos)
        st, raw, _ = req(ports["n2"], "GET", "/docs/soak0")
        got = set(json.loads(raw)["values"])
        missing = [v for v in acked if v not in got]
        assert not missing, (missing[:5], netchaos)
    finally:
        import signal
        for p in procs.values():
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
        for p in procs.values():
            try:
                p.wait(20)
            except subprocess.TimeoutExpired:
                p.kill()
