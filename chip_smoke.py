"""Smoke of the main path on the chip, in ONE process (a chip belongs to
one process at a time).

Default (one chip):

- ``kernel``: BASELINE config 5 at full size — 64 replicas x 1M ops
  (``bench.workloads.chain_workload``), ``hints="exhaustive"`` as
  bench.py runs it — through ``merge.materialize``.  The visible order
  must equal the closed form (``chain_expected_ts``), and the compiled
  text must hold a ``tpu_custom_call`` for every pallas kernel the
  production trace selects.  Prints compile seconds and one warm merge
  time (a smoke timing, not a benchmark).
- ``oracle``: config 2 (2 replicas, 10k interleaved ops) merged on the
  chip and compared with the ``core/`` oracle's replay.
- ``served``: ``make_server(port=0)`` with its default ServingEngine,
  as ``python -m crdt_graph_tpu.service`` starts it.  The config-5
  batch is POSTed as wire JSON to one document (a body over 1 MB takes
  the native column ingest); then 3 concurrent writers per document on
  2 more documents POST deltas of 4 x DELTA_THRESHOLD ops, staged so
  each round coalesces per document and reaches the scheduler's
  cross-document grouped launch.  Reads are compared with the oracle
  replay (and the closed form for the 1M document); every response must
  be 200; the server must shut down cleanly.

``--chips 4`` runs only what exists across chips: config 5 through
``opsaxis.routed_materialize`` against single-device
``merge.materialize`` (every NodeTable field bit-equal, operands on 4
devices), and one grouped served round on a 4-device docs mesh.

Any failed phase makes the exit code non-zero.  The last line of
stdout, on success only, is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""
import argparse
import json
import re
import sys
import threading
import time
import traceback
from http.client import HTTPConnection

N_REPLICAS = 64
N_OPS = 1_000_000
WRITERS = 3

# NodeTable fields compared bit for bit (__graft_entry__.py)
FIELDS = ("ts", "parent", "depth", "value_ref", "paths", "exists",
          "tombstone", "dead", "visible", "doc_index", "order",
          "visible_order", "num_nodes", "num_visible", "status")


class SmokeFailure(AssertionError):
    pass


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    log(f"ok   {what}")


def kernels_in(text: str) -> set:
    """Names of the pallas kernels compiled into an executable's text."""
    return set(re.findall(
        r'%([A-Za-z0-9_]+)(?:\.\d+)? = [^\n]*'
        r'custom_call_target="tpu_custom_call"', text))


def config5(n_replicas: int, n_ops: int):
    from crdt_graph_tpu.bench import workloads
    return (workloads.chain_workload(n_replicas, n_ops),
            workloads.chain_expected_ts(n_replicas, n_ops))


def visible_ts(table):
    import numpy as np
    seq = np.asarray(table.ts)[np.asarray(table.visible_order)]
    return seq[:int(table.num_visible)]


# ---- phases (one chip) ---------------------------------------------------

def phase_kernel(n_replicas: int = N_REPLICAS, n_ops: int = N_OPS) -> None:
    import jax
    import numpy as np

    from crdt_graph_tpu.codec import packed
    from crdt_graph_tpu.ops import fused_resolve, merge, mono_gather, \
        tour_scan

    ops, expected = config5(n_replicas, n_ops)
    # padded to the engine's power-of-two bucket, as the served path
    # packs the same batch: the served merge then reuses this compile
    ops = packed.pad_arrays(ops, packed._bucket(n_ops))
    nd = merge.host_no_deletes(ops["kind"])
    t0 = time.perf_counter()
    compiled = merge._materialize.lower(ops, None, "exhaustive",
                                        nd).compile()
    log(f"compile_s config5 kernel ({n_ops} ops in "
        f"{ops['kind'].shape[0]} rows): "
        f"{time.perf_counter() - t0:.3f}")
    found = kernels_in(compiled.as_text())
    for name in sorted(found):
        log(f"tpu_custom_call in compiled config-5 kernel: {name}")
    want = {fused_resolve.KERNEL_NAME2, mono_gather.KERNEL_NAME,
            tour_scan.KERNEL_NAME}
    check(want <= found, f"pallas kernels {sorted(want)} compiled into "
          f"the production trace")
    t0 = time.perf_counter()
    table = merge.materialize(ops, hints="exhaustive")
    jax.block_until_ready(table)
    log(f"first merge.materialize call s (compile cache hit expected): "
        f"{time.perf_counter() - t0:.3f}")
    check(int(table.num_visible) == expected.shape[0] and
          np.array_equal(visible_ts(table), expected),
          f"config-5 visible order equals the closed form "
          f"({expected.shape[0]} ops)")
    dev = jax.device_put(ops)
    jax.block_until_ready(dev)
    t0 = time.perf_counter()
    warm = merge._materialize(dev, None, "exhaustive", nd)
    jax.block_until_ready(warm)
    log(f"smoke timing, not a benchmark: one warm config-5 merge, "
        f"device-resident input: {(time.perf_counter() - t0) * 1e3:.3f} "
        f"ms")
    check(np.array_equal(np.asarray(warm.visible_order),
                         np.asarray(table.visible_order)),
          "warm merge repeats the first")


def phase_oracle(n_ops: int = 10_000) -> None:
    import crdt_graph_tpu as crdt
    from crdt_graph_tpu.bench import workloads
    from crdt_graph_tpu.codec import packed
    from crdt_graph_tpu.core.operation import Batch
    from crdt_graph_tpu.ops import merge, view

    raw = workloads.two_replica_interleaved(n_ops)
    p = packed.pack(raw)
    got = view.visible_values(merge.materialize(p.arrays()), p.values)
    want = crdt.init(99).apply(Batch(tuple(raw))).visible_values()
    check(len(got) == len(raw) and got == want,
          f"config 2 ({len(raw)} ops) equals the core/ oracle replay")


class Client:
    """One keep-alive HTTP connection per thread; every response must
    be 200."""

    def __init__(self, port: int):
        self.port = port
        self.local = threading.local()

    def request(self, method: str, path: str, body=None,
                timeout: float = 900.0) -> bytes:
        conn = getattr(self.local, "conn", None)
        if conn is None:
            conn = self.local.conn = HTTPConnection(
                "127.0.0.1", self.port, timeout=timeout)
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        raw = resp.read()
        if resp.status != 200:
            raise SmokeFailure(f"{method} {path} -> {resp.status}: "
                               f"{raw[:300]!r}")
        return raw

    def json(self, method: str, path: str, body=None):
        return json.loads(self.request(method, path, body))


def _post_config5(client, store, n_replicas: int, n_ops: int) -> None:
    """The 1M-op batch as wire JSON to one document, checked against
    the closed form."""
    import numpy as np

    from crdt_graph_tpu import native
    from crdt_graph_tpu.bench import workloads
    from crdt_graph_tpu.codec import json_codec
    from crdt_graph_tpu.core.operation import Batch

    ops, expected = config5(n_replicas, n_ops)
    n = expected.shape[0]
    body = json_codec.dumps(Batch(tuple(workloads.unpack_ops(ops)))
                            ).encode()
    codec = "native" if (len(body) >= store.wire_fast_bytes and
                         native.available()) else "python"
    log(f"config-5 POST body {len(body)} bytes ingested by the {codec} "
        f"codec")
    t0 = time.perf_counter()
    out = client.json("POST", "/docs/c5/ops", body)
    log(f"config-5 POST acked in s (compile included): "
        f"{time.perf_counter() - t0:.3f}")
    check(out.get("accepted") and out.get("applied_count") == n,
          f"config-5 POST applied {n} ops")
    # value_ref of (replica r, counter c) is (r-1)·per + c-1
    per = n_ops // n_replicas
    rid, ctr = expected >> 32, expected & 0xFFFFFFFF
    want_vals = ((rid - 1) * per + ctr - 1).tolist()
    got = client.json("GET", "/docs/c5")["values"]
    check(got == want_vals, "GET /docs/c5 equals the closed form")
    served = json.loads(client.request("GET", "/docs/c5/ops?since=0"))
    got_ts = np.array(sorted(o["ts"] for o in served["ops"]))
    check(np.array_equal(got_ts, np.sort(ops["ts"])),
          "GET /docs/c5/ops?since=0 holds exactly the posted ops")


def _writer_ops(rid: int, start: int, prev: int, n: int):
    from crdt_graph_tpu.core.operation import Add
    ops = []
    for c in range(start, start + n):
        ts = rid * 2**32 + c
        ops.append(Add(ts, (prev,), f"{rid}:{c}"))
        prev = ts
    return ops, prev


def _served_rounds(client, store, doc_ids, rounds: int,
                   delta: int) -> dict:
    """``WRITERS`` concurrent writers per document, ``rounds`` staged
    rounds: the scheduler is held while every writer's POST queues, so
    each round fuses per document (coalesce_width = WRITERS) and
    launches all documents in one cross-document group.  Returns
    {doc: every op posted to it}."""
    from crdt_graph_tpu.codec import json_codec
    from crdt_graph_tpu.core.operation import Batch

    writers = []
    for d in doc_ids:
        for _ in range(WRITERS):
            rid = client.json("POST", f"/docs/{d}/replicas")["replica"]
            writers.append({"doc": d, "rid": rid, "next": 1, "prev": 0})
    posted = {d: [] for d in doc_ids}
    errors = []
    sched = store.scheduler
    for r in range(rounds):
        bodies = []
        for w in writers:
            ops, w["prev"] = _writer_ops(w["rid"], w["next"], w["prev"],
                                         delta)
            w["next"] += delta
            posted[w["doc"]].extend(ops)
            bodies.append((w["doc"], json_codec.dumps(Batch(tuple(ops)))))

        def post(doc, body):
            try:
                out = client.json("POST", f"/docs/{doc}/ops", body)
                if out.get("applied_count") != delta:
                    errors.append(f"{doc}: {out}")
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(f"{doc}: {e!r}")

        sched.pause()
        try:
            threads = [threading.Thread(target=post, args=b)
                       for b in bodies]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and not errors and any(
                    len(store.get(d).queue) < WRITERS for d in doc_ids):
                time.sleep(0.01)
        finally:
            sched.resume()
        for t in threads:
            t.join(900)
        check(not errors and not any(t.is_alive() for t in threads),
              f"round {r}: {len(bodies)} concurrent POSTs acked "
              f"{delta} ops each {errors[:2]}")
    return posted


def _check_served_docs(client, posted: dict) -> None:
    import crdt_graph_tpu as crdt
    from crdt_graph_tpu.core.operation import Batch

    for d, ops in posted.items():
        want = crdt.init(99).apply(Batch(tuple(ops))).visible_values()
        got = client.json("GET", f"/docs/{d}")["values"]
        check(got == want, f"GET /docs/{d} equals the oracle replay "
              f"({len(ops)} ops)")
        served = json.loads(client.request("GET", f"/docs/{d}/ops?since=0"))
        check(sorted(o["ts"] for o in served["ops"]) ==
              sorted(o.ts for o in ops),
              f"GET /docs/{d}/ops?since=0 holds exactly the posted ops")
        width = client.json("GET", f"/docs/{d}/metrics")["coalesce_width"]
        check(width.get("max", 0) >= 2,
              f"/docs/{d}/metrics coalesce_width max {width.get('max')} "
              f">= 2")


def _start_server():
    from crdt_graph_tpu.service import make_server
    srv = make_server(port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _stop_server(srv) -> None:
    srv.shutdown()
    srv.server_close()
    srv.store.scheduler.join(60)
    check(not srv.store.scheduler.is_alive(),
          "server and scheduler shut down cleanly")


def phase_served(n_replicas: int = N_REPLICAS, n_ops: int = N_OPS,
                 n_docs: int = 2, rounds: int = 2) -> None:
    from crdt_graph_tpu import engine

    srv = _start_server()
    try:
        client = Client(srv.server_port)
        store = srv.store
        _post_config5(client, store, n_replicas, n_ops)
        doc_ids = [f"w{i}" for i in range(n_docs)]
        batches0 = store.counters.get("cross_doc_batches")
        posted = _served_rounds(client, store, doc_ids, rounds,
                                4 * engine.DELTA_THRESHOLD)
        launched = store.counters.get("cross_doc_batches") - batches0
        check(launched >= 1, f"{launched} cross-document grouped "
              f"launches")
        _check_served_docs(client, posted)
    finally:
        _stop_server(srv)


# ---- phases (four chips) -------------------------------------------------

def phase_opsaxis(n_replicas: int = N_REPLICAS, n_ops: int = N_OPS,
                  k: int = 4) -> None:
    import jax
    import numpy as np

    from crdt_graph_tpu.codec import packed
    from crdt_graph_tpu.ops import merge
    from crdt_graph_tpu.parallel import opsaxis

    ops, expected = config5(n_replicas, n_ops)
    ops = packed.pad_arrays(ops, packed._bucket(n_ops))   # as served
    check(opsaxis.enabled_for(ops["kind"].shape[0]) and
          opsaxis.mesh_devices() == k,
          f"the serving route shards {n_ops} ops over {k} devices")
    seen = []
    real = opsaxis._opsaxis_materialize

    def spy(device_ops, *a, **kw):
        seen.extend(len(v.sharding.device_set)
                    for v in device_ops.values())
        return real(device_ops, *a, **kw)

    opsaxis._opsaxis_materialize = spy
    try:
        t0 = time.perf_counter()
        sharded = opsaxis.routed_materialize(ops, "exhaustive")
        jax.block_until_ready(sharded)
        log(f"ops-axis routed merge s (compile included): "
            f"{time.perf_counter() - t0:.3f}")
    finally:
        opsaxis._opsaxis_materialize = real
    check(seen and all(n == k for n in seen),
          f"every sharded operand spans {k} devices")
    t0 = time.perf_counter()
    single = merge.materialize(ops, hints="exhaustive")
    jax.block_until_ready(single)
    log(f"single-device merge s (compile included): "
        f"{time.perf_counter() - t0:.3f}")
    check(len(single.ts.sharding.device_set) == 1,
          "the reference merge ran on one device")
    for f in FIELDS:
        check(np.array_equal(np.asarray(getattr(sharded, f)),
                             np.asarray(getattr(single, f))),
              f"NodeTable.{f} bit-equal, sharded vs single device")
    check(np.array_equal(visible_ts(sharded), expected),
          "sharded config-5 visible order equals the closed form")


def phase_docs_mesh(k: int = 4) -> None:
    import jax

    from crdt_graph_tpu import engine
    from crdt_graph_tpu.parallel import mesh as mesh_mod

    spans = []
    real = mesh_mod.batched_materialize

    def spy(*a, **kw):
        table = real(*a, **kw)
        spans.append(len(table.ts.sharding.device_set))
        return table

    mesh_mod.batched_materialize = spy
    srv = _start_server()
    try:
        client = Client(srv.server_port)
        posted = _served_rounds(client, srv.store,
                                [f"m{i}" for i in range(k)], 1,
                                4 * engine.DELTA_THRESHOLD)
        jax.effects_barrier()
        check(spans and all(n == k for n in spans),
              f"grouped launch of {k} documents spans {spans} devices")
        _check_served_docs(client, posted)
    finally:
        mesh_mod.batched_materialize = real
        _stop_server(srv)


# ---- driver ----------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the paths that span four chips")
    args = ap.parse_args(argv)

    import jax

    from crdt_graph_tpu.utils import compcache, hostenv

    cache_dir = compcache.enable()
    jax.config.update("jax_enable_x64", True)
    devs = hostenv.require_tpu("chip_smoke.py")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke.py: --chips {args.chips} but JAX "
                         f"found {len(devs)} device(s)")
    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{cache_dir}")
    phases = ([phase_opsaxis, phase_docs_mesh] if args.chips == 4 else
              [phase_kernel, phase_oracle, phase_served])
    failed = []
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        t0 = time.perf_counter()
        log(f"phase {name} ...")
        try:
            phase()
        except Exception:  # noqa: BLE001 — every phase reports
            traceback.print_exc()
            failed.append(name)
        log(f"phase {name} {'FAILED' if name in failed else 'passed'} "
            f"in {time.perf_counter() - t0:.3f} s")
    if failed:
        log(f"FAILED phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
