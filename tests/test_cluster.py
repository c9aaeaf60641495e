"""The replica fleet (crdt_graph_tpu/cluster/, ISSUE 7): coordination
KV, consistent-hash ring, replica-id leases, the bounded anti-entropy
wire, and the in-process 3-server fleet — forwarding, replica-local
reads, deterministic chaos (kill the primary mid-queued-merge, operator
failover, fingerprint-equal convergence, crash-safe rejoin under a
bumped fencing epoch), plus the ``crdt_cluster_*`` exposition under the
strict prom naming contract.

The slow-marked soak at the bottom runs the same story against REAL
processes (``python -m crdt_graph_tpu.cluster`` over a shared FileKV
spool) with an actual ``SIGKILL`` — the one failure shape an in-process
crash cannot model (a merge dying mid-kernel).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
from http.client import HTTPConnection

import pytest

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from crdt_graph_tpu import engine
from crdt_graph_tpu.cluster import (FileKV, FleetServer, HashRing,
                                    LeaseError, LeaseLost, LeaseService,
                                    MemoryKV)
from crdt_graph_tpu.cluster import kv as kv_mod
from crdt_graph_tpu.codec import json_codec
from crdt_graph_tpu.codec import packed as packed_mod
from crdt_graph_tpu.core.operation import Add, Batch, Delete
from crdt_graph_tpu.obs import prom as prom_mod


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


# -- coordination KV ---------------------------------------------------------


def _kv_contract(kv):
    assert kv.get("a") is None
    assert kv.cas("a", "v1", 0)            # create
    assert not kv.cas("a", "v2", 0)        # create-only loses to exist
    assert kv.get("a") == ("v1", 1)
    assert kv.cas("a", "v2", 1)            # versioned update
    assert not kv.cas("a", "v3", 1)        # stale version loses
    assert kv.get("a") == ("v2", 2)
    assert kv.cas("lease/3", "x", 0)       # path-like keys
    assert kv.keys("lease/") == ["lease/3"]
    assert not kv.delete("a", 1)           # stale delete loses
    assert kv.delete("a", 2)
    assert kv.get("a") is None
    assert kv.keys() == ["lease/3"]


def test_memory_kv_contract():
    _kv_contract(MemoryKV())


def test_file_kv_contract_and_cross_instance(tmp_path):
    root = str(tmp_path / "spool")
    _kv_contract(FileKV(root))
    # a second instance over the same spool sees the same store (the
    # many-process, one-host deployment)
    a, b = FileKV(root), FileKV(root)
    assert a.cas("shared", "from-a", 0)
    assert b.get("shared") == ("from-a", 1)
    assert b.cas("shared", "from-b", 1)
    assert a.get("shared") == ("from-b", 2)


@pytest.mark.parametrize("make", [
    lambda tmp: MemoryKV(), lambda tmp: FileKV(str(tmp / "ctr"))],
    ids=["memory", "file"])
def test_kv_counter_unique_under_threads(tmp_path, make):
    kv = make(tmp_path)
    n_threads, per = 8, 12
    got = [[] for _ in range(n_threads)]

    def worker(i):
        for _ in range(per):
            got[i].append(kv_mod.next_counter(kv, "replica/doc"))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    flat = [v for g in got for v in g]
    assert sorted(flat) == list(range(1, n_threads * per + 1))


# -- consistent-hash ring ----------------------------------------------------


def test_ring_deterministic_and_balanced():
    members = {"a": "h:1", "b": "h:2", "c": "h:3"}
    docs = [f"d{i}" for i in range(300)]
    r1, r2 = HashRing(members), HashRing(dict(members))
    assert [r1.primary(d) for d in docs] == [r2.primary(d) for d in docs]
    spread = r1.spread(docs)
    assert set(spread) == set(members)
    assert all(v > 0 for v in spread.values()), spread
    for d in docs[:20]:
        pref = r1.preference(d)
        assert pref[0] == r1.primary(d)
        assert sorted(pref) == sorted(members)


def test_ring_minimal_rebalance_on_member_loss():
    """Dropping one member moves ONLY its documents; everything else
    keeps its primary — the property that makes failover cheap."""
    docs = [f"d{i}" for i in range(300)]
    r3 = HashRing({"a": "h:1", "b": "h:2", "c": "h:3"})
    r2 = HashRing({"a": "h:1", "b": "h:2"})
    moved = 0
    for d in docs:
        before = r3.primary(d)
        if before == "c":
            moved += 1
            assert r2.primary(d) in ("a", "b")
        else:
            assert r2.primary(d) == before, d
    assert 0 < moved < len(docs)
    # and the failover target was the doc's next preference all along
    for d in docs:
        if r3.primary(d) == "c":
            assert r2.primary(d) == [m for m in r3.preference(d)
                                     if m != "c"][0]


def test_ring_empty_and_single():
    assert HashRing({}).primary("x") is None
    assert HashRing({}).preference("x") == []
    assert HashRing({"only": "h:1"}).primary("x") == "only"


# -- replica-id leases -------------------------------------------------------


def test_lease_protocol_fencing_and_reclaim():
    now = [1000.0]
    svc = LeaseService(MemoryKV(), ttl_s=5.0, max_ids=4,
                       clock=lambda: now[0])
    a = svc.acquire("alice", "h:1")
    b = svc.acquire("bob", "h:2")
    assert (a.id, b.id) == (0, 1)
    assert a.token == b.token == 1
    assert set(svc.members()) == {"alice", "bob"}

    # renewal extends; release frees the slot immediately
    now[0] += 3.0
    a = svc.renew(a)
    assert a.expires == now[0] + 5.0
    assert svc.release(b)
    assert set(svc.members()) == {"alice"}

    # natural expiry: the slot becomes claimable, the claim BUMPS the
    # fencing token, and the deposed holder's renew is refused
    now[0] += 10.0
    assert svc.members() == {}
    c = svc.acquire("carol", "h:3")
    assert c.id == 0 and c.token == 2       # alice's slot, fenced
    with pytest.raises(LeaseLost):
        svc.renew(a)
    assert not svc.release(a)

    # crash-safe re-acquisition: the SAME name reclaims its own slot
    # immediately — no TTL wait — with a bumped token (the dead
    # incarnation is fenced the moment the CAS lands)
    c2 = svc.acquire("carol", "h:3b")
    assert c2.id == c.id and c2.token == c.token + 1
    with pytest.raises(LeaseLost):
        svc.renew(c)

    # operator force-expiry keeps the token; the next claimant bumps it
    assert svc.expire_now("carol")
    assert "carol" not in svc.members()
    d = svc.acquire("dave", "h:4")
    assert d.id == c2.id and d.token == c2.token + 1


def test_lease_fleet_full():
    svc = LeaseService(MemoryKV(), ttl_s=60.0, max_ids=2)
    svc.acquire("a", "h:1")
    svc.acquire("b", "h:2")
    with pytest.raises(LeaseError):
        svc.acquire("c", "h:3")


# -- the anti-entropy window (packed_since_window) ---------------------------


def _mixed_log():
    """A log with interleaved adds and deletes ENDING on deletes, so
    window trimming (a window must end on an Add) is exercised."""
    ops, prev = [], 0
    for c in range(1, 13):
        ops.append(Add(ts(1, c), (prev,), f"v{c}"))
        prev = ts(1, c)
        if c % 4 == 0:
            ops.append(Delete((ts(1, c - 1),)))
    ops.append(Delete((ts(1, 12),)))        # trailing deletes
    ops.append(Delete((ts(1, 11),)))
    return ops


def test_window_unbounded_matches_since_bytes():
    p = packed_mod.pack(_mixed_log())
    for since in (0, ts(1, 1), ts(1, 6), ts(1, 12)):
        wire, meta = engine.packed_since_window(p, since, 0)
        assert wire == engine.packed_since_bytes(p, since)
        assert meta["found"] and not meta["more"]


def test_window_boundary_at_exact_timestamp():
    p = packed_mod.pack(_mixed_log())
    # the since terminator is served INCLUSIVELY (the overlap row
    # absorbs as a duplicate at the puller) — the boundary the
    # reference's operationsSince contract pins
    wire, meta = engine.packed_since_window(p, ts(1, 6), 5)
    got = json_codec.loads(wire.decode())
    assert got.ops[0].ts == ts(1, 6)
    assert meta["found"] and meta["count"] >= 1
    # a mark equal to the LAST Add: just the terminator row (plus any
    # trailing deletes) — and never "more"
    wire, meta = engine.packed_since_window(p, ts(1, 12), 5)
    got = json_codec.loads(wire.decode())
    assert got.ops[0].ts == ts(1, 12)
    assert not meta["more"]
    # an unknown timestamp — and a DELETE's timestamp, which is never
    # a valid terminator — both report found=False so the puller
    # resets its mark instead of spinning
    for bogus in (ts(1, 99), ts(2, 1)):
        wire, meta = engine.packed_since_window(p, bogus, 5)
        assert not meta["found"] and meta["count"] == 0
        assert wire == b'{"op":"batch","ops":[]}'


def test_window_chain_resumes_and_ends_on_adds():
    ops = _mixed_log()
    p = packed_mod.pack(ops)
    add_ts = {op.ts for op in ops if isinstance(op, Add)}
    since, windows, metas = 0, [], []
    for _ in range(50):
        wire, meta = engine.packed_since_window(p, since, 3)
        assert meta["found"]
        windows.append(wire)
        metas.append(meta)
        if meta["next_since"] is not None:
            assert meta["next_since"] in add_ts    # resumable marks
            since = meta["next_since"]
        if not meta["more"]:
            break
    else:
        pytest.fail("window chain never terminated")
    assert len(windows) > 2                        # actually windowed
    assert sum(m["count"] for m in metas) >= p.num_ops  # overlap rows
    # reassembly: a fresh replica applying the chained windows equals
    # one applying the full log in one shot
    t_full, t_chain = engine.init(7), engine.init(7)
    t_full.apply(json_codec.loads(
        engine.packed_since_bytes(p, 0).decode()))
    for wire in windows:
        t_chain.apply(json_codec.loads(wire.decode()))
    assert t_chain.visible_values() == t_full.visible_values()


def test_window_exchange_idempotent_and_commutative():
    """Interleaved peer exchanges over the windowed wire: any delivery
    order, any duplication, same converged state — idempotence and
    commutativity are the CRDT's, the windows only have to preserve
    them (incl. the inclusive-terminator overlap rows)."""
    a, b = engine.init(1), engine.init(2)
    for i in range(1, 19):
        a.add(f"a{i}")
        if i % 5 == 0:
            prev = a.operations_since(0).ops[-2]
            a.delete(prev.path[:-1] + (prev.ts,))
    for i in range(1, 14):
        b.add(f"b{i}")

    def windows(tree, limit):
        p = packed_mod.pack(tuple(tree.operations_since(0).ops))
        since, out = 0, []
        while True:
            wire, meta = engine.packed_since_window(p, since, limit)
            out.append(json_codec.loads(wire.decode()))
            if meta["next_since"] is not None:
                since = meta["next_since"]
            if not meta["more"]:
                return out

    wa, wb = windows(a, 4), windows(b, 3)
    # a pulls b, b pulls a — opposite window orders are NOT possible
    # (windows chain), but interleaving ACROSS peers is free
    for w in wb:
        a.apply(w)
    for w in wa:
        b.apply(w)
    assert a.visible_values() == b.visible_values()
    # a third replica hears everything late, duplicated, interleaved
    c = engine.init(3)
    for w in (wb[0], *wa, *wb, *wa[::1], wb[-1]):
        c.apply(w)
    assert c.visible_values() == a.visible_values()
    # idempotence: replaying every window changes nothing
    before = a.visible_values()
    for w in (*wa, *wb):
        a.apply(w)
    assert a.visible_values() == before


# -- in-process fleet --------------------------------------------------------


def _spawn_fleet(kv, names, **kw):
    """Deterministic fleet: huge TTL (no renew races), dormant
    anti-entropy daemon (tests drive ``sync_now`` themselves)."""
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


def _chain(rid, n, start=1, prev=0):
    ops = []
    for c in range(start, start + n):
        ops.append(Add(ts(rid, c), (prev,), f"r{rid}:{c}"))
        prev = ts(rid, c)
    return json_codec.dumps(Batch(tuple(ops)))


def _state_fp(fleet_server, doc):
    st, raw, hdr = req(fleet_server.port, "GET", f"/docs/{doc}")
    assert st == 200, raw
    return hdr["X-State-Fingerprint"], json.loads(raw)["values"], hdr


def test_fleet_forwarding_replica_reads_and_convergence():
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1", "n2"))
    try:
        ring = fleet["n0"].node.ring()
        assert len(ring) == 3
        doc = _doc_owned_by(ring, "n1")

        # fleet-unique client replica ids, allocated via ANY server
        rids = [json.loads(req(fs.port, "POST",
                               f"/docs/{doc}/replicas")[1])["replica"]
                for fs in fleet.values() for _ in range(2)]
        assert sorted(rids) == list(range(1, 7))

        # a write entering through a NON-primary lands on the primary
        st, raw, hdr = req(fleet["n0"].port, "POST", f"/docs/{doc}/ops",
                           body=_chain(rids[0], 5),
                           headers={"X-Trace-Id": "fleet-fwd-00000001"})
        out = json.loads(raw)
        assert st == 200 and out["accepted"], out
        assert out["served_by"]["name"] == "n1"
        assert out["trace_id"] == "fleet-fwd-00000001"  # echo survives
        assert fleet["n0"].node.counters["forwarded_ok"] >= 1
        assert fleet["n1"].node.counters["forwarded_in"] >= 1

        # replica-local reads: the primary has it NOW (read-your-writes
        # through the committing node); a peer does not until it syncs
        fp1, values1, hdr1 = _state_fp(fleet["n1"], doc)
        assert values1 == [f"r{rids[0]}:{c}" for c in range(1, 6)]
        assert hdr1["X-Replica-Name"] == "n1"
        assert hdr1["X-Replica-Id"] == str(fleet["n1"].node.node_id())
        assert hdr1["X-Replica-Epoch"] == "1"
        assert "X-Commit-Seq" in hdr1 and "X-Snapshot-Fingerprint" in hdr1
        st, raw, _ = req(fleet["n2"].port, "GET", f"/docs/{doc}")
        # n2 materialized an empty local doc when it allocated replica
        # ids above; the write itself has not synced yet — honest
        assert st == 200 and json.loads(raw)["values"] == []

        # one anti-entropy round per peer and the fleet is converged,
        # with the replica-INDEPENDENT fingerprint agreeing everywhere
        # (X-Commit-Seq legitimately differs per server)
        for fs in fleet.values():
            fs.node.antientropy.sync_now()
        for fs in fleet.values():
            fp, values, _ = _state_fp(fs, doc)
            assert values == values1, fs.name
            assert fp == fp1, fs.name

        # the windowed pull surface over HTTP: bounded, resumable
        st, raw, hdr = req(fleet["n1"].port, "GET",
                           f"/docs/{doc}/ops?since=0&limit=2")
        assert st == 200 and hdr["X-Since-Found"] == "1"
        assert hdr["X-Since-More"] == "1"
        assert int(hdr["X-Since-Next"]) == ts(rids[0], 2)
        st, raw, hdr = req(fleet["n1"].port, "GET",
                           f"/docs/{doc}/ops?since=12345&limit=2")
        assert st == 200 and hdr["X-Since-Found"] == "0"

        # /cluster introspection + the crdt_cluster_* prom families
        # under the SAME strict naming contract as everything else
        st, raw, _ = req(fleet["n2"].port, "GET", "/cluster")
        view = json.loads(raw)
        assert set(view["members"]) == {"n0", "n1", "n2"}
        assert view["node"]["name"] == "n2"
        assert view["antientropy"]["rounds"] >= 1
        st, raw, _ = req(fleet["n2"].port, "GET", "/metrics/prom")
        fams = prom_mod.parse_text(raw.decode())
        for fam in ("crdt_cluster_members", "crdt_cluster_node_id",
                    "crdt_cluster_lease_epoch",
                    "crdt_cluster_forwarded_ok_total",
                    "crdt_cluster_antientropy_rounds_total",
                    "crdt_cluster_antientropy_round_ms",
                    "crdt_cluster_antientropy_sync_age_seconds",
                    "crdt_cluster_antientropy_ops_applied_total"):
            assert fam in fams, fam
        peers = {lbl["peer"] for _, lbl, _ in
                 fams["crdt_cluster_antientropy_ops_applied_total"]
                 ["samples"]}
        assert peers == {"n0", "n1"}
    finally:
        _stop_fleet(fleet)


def test_fleet_antientropy_mark_reset_on_lost_peer_log():
    """A peer that no longer knows our high-water mark (restarted with
    a fresh log) answers X-Since-Found: 0 — the puller resets to 0 and
    re-pulls from scratch instead of spinning on empty windows."""
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1"))
    try:
        doc = _doc_owned_by(fleet["n0"].node.ring(), "n0")
        st, raw, _ = req(fleet["n0"].port, "POST", f"/docs/{doc}/ops",
                         body=_chain(7, 6))
        assert st == 200
        ae = fleet["n1"].node.antientropy
        assert ae.sync_now() == {"n0": True}
        last = ts(7, 6)
        assert ae._peers["n0"].hw[doc] == last
        fp0, _, _ = _state_fp(fleet["n0"], doc)
        fp1, _, _ = _state_fp(fleet["n1"], doc)
        assert fp0 == fp1
        # poison the mark (models: n0 restarted with an empty log and
        # refilled differently — our mark no longer resolves there)
        ae._peers["n0"].hw[doc] = ts(9, 999)
        assert ae.sync_now() == {"n0": True}
        assert ae._peers["n0"].hw[doc] == last   # reset + re-pulled
        fp1b, _, _ = _state_fp(fleet["n1"], doc)
        assert fp1b == fp0                       # duplicates absorbed
    finally:
        _stop_fleet(fleet)


def test_fleet_chaos_kill_failover_converge_rejoin():
    """The tier-1 chaos round, fully deterministic: the victim's
    scheduler is PAUSED so a forwarded write is queued-but-unmerged
    when the crash lands (the in-flight client gets an honest 503 and
    re-pushes through a survivor), failover is operator-forced
    (``expire_now`` — no TTL sleep), survivors converge to
    fingerprint-equal snapshots, and the victim rejoins under its old
    name with a bumped fencing epoch and syncs back to equality."""
    kv = MemoryKV()
    fleet = _spawn_fleet(kv, ("n0", "n1", "n2"))
    try:
        ring = fleet["n0"].node.ring()
        doc = _doc_owned_by(ring, "n1", prefix="chaos")
        victim = fleet["n1"]

        # seed state through every server (all forwarded to n1), sync
        for i, fs in enumerate(fleet.values()):
            st, raw, _ = req(fs.port, "POST", f"/docs/{doc}/ops",
                             body=_chain(10 + i, 4))
            assert st == 200, raw
        for fs in fleet.values():
            fs.node.antientropy.sync_now()
        fp_seed, _, _ = _state_fp(fleet["n0"], doc)

        # stage the kill: giant delta forwarded to the paused primary
        victim.node.engine.scheduler.pause()
        result = {}

        def giant():
            st, raw, _ = req(fleet["n0"].port, "POST",
                             f"/docs/{doc}/ops",
                             body=_chain(42, 300), timeout=120)
            result["status"], result["raw"] = st, raw

        th = threading.Thread(target=giant, daemon=True)
        th.start()
        deadline = time.monotonic() + 60
        while victim.node.engine.scheduler_metrics()[
                "queue_depth_total"] < 1:
            assert time.monotonic() < deadline, \
                "giant never reached the victim's queue"
            time.sleep(0.01)

        victim.crash()                      # no drain, no lease release
        th.join(120)
        assert result["status"] == 503, result  # honest failure, not a hang

        # lease-table failover, operator-forced (deterministic)
        assert fleet["n0"].node.leases.expire_now("n1")
        for n in ("n0", "n2"):
            fleet[n].node.refresh_ring()
        new_primary = fleet["n0"].node.primary_for(doc)
        assert new_primary in ("n0", "n2")

        # the client re-pushes the SAME delta through a survivor —
        # idempotent by CRDT construction
        st, raw, _ = req(fleet["n0"].port, "POST", f"/docs/{doc}/ops",
                         body=_chain(42, 300), timeout=120)
        out = json.loads(raw)
        assert st == 200 and out["accepted"], out
        assert out["served_by"]["name"] == new_primary

        # survivors converge; fingerprints equal and state moved on
        for n in ("n0", "n2"):
            fleet[n].node.antientropy.sync_now()
        fp0, values0, _ = _state_fp(fleet["n0"], doc)
        fp2, values2, _ = _state_fp(fleet["n2"], doc)
        assert fp0 == fp2 and fp0 != fp_seed
        assert values0 == values2
        assert "r42:300" in values0

        # rejoin under the old name: same slot, bumped fencing epoch,
        # anti-entropy refills the state to fingerprint equality
        reborn = FleetServer("n1", kv, ttl_s=600.0,
                             ae_interval_s=3600.0)
        fleet["n1"] = reborn
        assert reborn.node.node_id() == victim.node.node_id()
        assert reborn.node.epoch() == victim.node.epoch() + 1
        for fs in fleet.values():
            fs.node.refresh_ring()
        reborn.node.antientropy.sync_now()
        fp1, values1, hdr1 = _state_fp(reborn, doc)
        assert fp1 == fp0 and values1 == values0
        assert hdr1["X-Replica-Epoch"] == str(reborn.node.epoch())
    finally:
        _stop_fleet(fleet)


# -- the real thing: processes, SIGKILL, restart -----------------------------


def _proc_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "true"
    return env


def _spawn_node(name, spool, ttl=2.0):
    proc = subprocess.Popen(
        [sys.executable, "-m", "crdt_graph_tpu.cluster", "--cpu",
         "--name", name, "--kv-dir", spool, "--port", "0",
         "--ttl", str(ttl), "--ae-interval", "0.2"],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env=_proc_env(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    line = proc.stdout.readline()
    assert line.startswith("READY "), line
    return proc, json.loads(line[len("READY "):])


@pytest.mark.slow
def test_fleet_soak_sigkill_primary_mid_merge(tmp_path):
    """3 real server processes over a shared FileKV spool; the
    primary of the giant's doc dies by SIGKILL mid-merge (the lease is
    NOT released — peers fail it over on TTL expiry), the giant
    re-pushes through a survivor, the victim restarts under its old
    name (bumped fencing epoch) and the fleet converges to
    fingerprint-equal snapshots everywhere."""
    spool = str(tmp_path / "fleet-kv")
    procs, infos = {}, {}
    try:
        for n in ("n0", "n1", "n2"):
            procs[n], infos[n] = _spawn_node(n, spool)
        ports = {n: int(i["addr"].rsplit(":", 1)[1])
                 for n, i in infos.items()}
        # wait until every node's ring sees the whole fleet
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            views = {n: json.loads(req(p, "GET", "/cluster")[1])
                     for n, p in ports.items()}
            if all(len(v["members"]) == 3 for v in views.values()):
                break
            time.sleep(0.1)
        else:
            pytest.fail("fleet membership never stabilized")

        doc = "soak0"
        # route discovery: the seed write's served_by names the primary
        st, raw, _ = req(ports["n0"], "POST", f"/docs/{doc}/ops",
                         body=_chain(5, 3))
        assert st == 200
        victim = json.loads(raw)["served_by"]["name"]
        survivors = [n for n in procs if n != victim]

        # giant push through a SURVIVOR entry (forwarded to the
        # victim), killed mid-merge
        giant_body = _chain(6, 60_000)
        result = {}

        def push_giant():
            entry = ports[survivors[0]]
            dl = time.monotonic() + 300
            while time.monotonic() < dl:
                try:
                    st, raw, _ = req(entry, "POST",
                                     f"/docs/{doc}/ops",
                                     body=giant_body, timeout=300)
                except OSError:
                    time.sleep(0.5)
                    continue
                if st == 200:
                    result["ack"] = json.loads(raw)
                    return
                time.sleep(0.5)     # 429/503: retry through failover
            result["error"] = "giant never acked"

        th = threading.Thread(target=push_giant, daemon=True)
        th.start()
        time.sleep(0.6)             # let the merge start at the victim
        procs[victim].send_signal(signal.SIGKILL)
        procs[victim].wait(30)

        th.join(300)
        assert "ack" in result, result
        # acked by a live server (the victim may have acked first if
        # the kill lost the race — then failover still must complete)
        procs.pop(victim).stdout.close()
        p_new, info_new = _spawn_node(victim, spool)
        procs[victim] = p_new
        assert info_new["epoch"] >= 2      # fenced past the dead one
        ports[victim] = int(info_new["addr"].rsplit(":", 1)[1])

        # convergence: every node reports the SAME replica-independent
        # state fingerprint and the giant's 60k values
        deadline = time.monotonic() + 180
        fps = {}
        while time.monotonic() < deadline:
            fps = {}
            for n, p in ports.items():
                try:
                    st, raw, hdr = req(p, "GET", f"/docs/{doc}")
                except OSError:
                    break
                if st != 200:
                    break
                fps[n] = hdr["X-State-Fingerprint"]
            if len(fps) == 3 and len(set(fps.values())) == 1:
                break
            time.sleep(0.5)
        assert len(set(fps.values())) == 1, fps
        st, raw, _ = req(ports[victim], "GET", f"/docs/{doc}")
        values = json.loads(raw)["values"]
        assert len(values) == 60_000 + 3
    finally:
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


# -- loadgen fleet mode (bench/loadgen.py run_fleet) -------------------------


def test_fleet_loadgen_smoke():
    """Tier-1 closed-loop fleet smoke: concurrent sessions through 3
    servers, sprayed replica-local reads, anti-entropy lag probes, and
    the oracle's cross-replica convergence check actually biting on
    more than one server — zero violations, zero session errors."""
    from crdt_graph_tpu.bench import loadgen
    cfg = loadgen.LoadgenConfig(
        n_servers=3, n_sessions=6, n_docs=2, writes_per_session=4,
        delta_size=6, giant_ops=0, kill_mid_run=False,
        lag_probe_every=2, lease_ttl_s=3.0, ae_interval_s=0.1, seed=3)
    rep = loadgen.run_fleet(cfg)
    assert rep["errors"] == [], rep["errors"]
    assert rep["violations"] == []
    assert rep["oracle"]["violations_total"] == 0
    # every doc fingerprint-converged across all three replicas, and
    # the convergence check ran per doc over the replica set
    assert len(rep["converged"]) == 2
    assert rep["oracle"]["checks"]["convergence"] >= 2
    # reads really were served by non-primary replicas, and lag was
    # actually measured ack -> visible-on-another-replica
    assert rep["reads_replica"] > 0
    assert rep["lag_probes"] > 0 and rep["lag_p99_s"] is not None
    # anti-entropy lag is first-class on the scrape surface
    assert "crdt_cluster_antientropy_sync_age_seconds" in \
        rep["prom_cluster_families"]
    assert "crdt_cluster_antientropy_round_ms" in \
        rep["prom_cluster_families"]


@pytest.mark.slow
def test_fleet_headline_full(tmp_path):
    """The committed-artifact run (BENCH_FLEET_r01_cpu.json shape):
    3 servers, concurrent sessions + giant racer, mid-merge kill with
    lease failover and rejoin, zero violations, fingerprint-equal
    convergence.  Slow-marked — tier-1 runs the smoke above."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bench_fleet_headline",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "bench_fleet_headline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.run(out_path=str(tmp_path / "BENCH_FLEET_test.json"))
    rep = out["report"]
    assert out["violations_total"] == 0
    assert not rep["errors"], rep["errors"]
    assert out["servers"] == 3 and out["sessions"] >= 60
    assert out["total_leaves"] >= 40_000
    assert rep["kill"] and "failover_s" in rep["kill"]
    assert rep["kill"]["rejoined_epoch"] >= 2
    assert out["converged_docs"] == 6
    assert out["antientropy_lag_p99_s"] is not None
    assert out["read_replica_p99_ms"] is not None
