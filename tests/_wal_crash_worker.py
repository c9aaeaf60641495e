"""Crash-point worker for tests/test_wal.py (ISSUE 9).

One run = one kill site: serve a durable document over real HTTP,
record every acked write to an append-only ack log (line-buffered — a
killed process's written bytes survive in the page cache exactly like
the WAL's), then arm ``GRAFT_CRASH_POINT=<site>`` + ``GRAFT_CRASH_EXIT``
and keep writing until the process dies hard (``os._exit(137)``) at the
armed durability boundary.  The parent asserts the 137, recovers a
fresh engine from the same durable dir, and checks ZERO acked-write
loss plus window byte-identity — the oracle contract of the crash
matrix.

Traffic is shaped so every site is reachable within one armed commit:
tiny hot budget (spills every couple of commits), fold-every-spill GC,
and a wide post-arm batch that forces spill + fold + manifest in the
same commit the WAL barrier fsyncs.

Usage: python tests/_wal_crash_worker.py SITE DURABLE_DIR ACK_LOG \
           [shared]
"""
import json
import os
import sys
import threading

SITE, DDIR, ACK_LOG = sys.argv[1], sys.argv[2], sys.argv[3]
SHARED = len(sys.argv) > 4 and sys.argv[4] == "shared"

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"
os.environ["GRAFT_OPLOG_HOT_OPS"] = "8"
os.environ["GRAFT_OPLOG_GC_SEGS"] = "1"
# tiny materialization cadence: the armed wide commit must cross the
# matz refresh too, so the mid-matz-write site fires within one commit
os.environ["GRAFT_MATZ_TAIL_OPS"] = "8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from http.client import HTTPConnection  # noqa: E402

from crdt_graph_tpu.codec import json_codec  # noqa: E402
from crdt_graph_tpu.core.operation import Add, Batch  # noqa: E402
from crdt_graph_tpu.obs import flight as flight_mod  # noqa: E402
from crdt_graph_tpu.serve import ServingEngine  # noqa: E402
from crdt_graph_tpu.service import make_server  # noqa: E402

OFF = 2**32
PRELUDE_ACKS = 4          # committed-and-durable history before arming


def main() -> None:
    engine = ServingEngine(durable_dir=DDIR, wal_sync="batch",
                           wal_shared=SHARED,
                           flight=flight_mod.FlightRecorder(),
                           submit_timeout_s=10.0)
    srv = make_server(port=0, store=engine)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    port = srv.server_port

    ack_f = open(ACK_LOG, "a")
    counter = 0
    prev = 0

    def chain(n):
        nonlocal counter, prev
        ops = []
        for _ in range(n):
            counter += 1
            ts = 1 * OFF + counter
            ops.append(Add(ts, (prev,), f"v{counter}"))
            prev = ts
        return ops

    conn = HTTPConnection("127.0.0.1", port, timeout=15)
    acked = 0
    for i in range(60):
        width = 20 if acked >= PRELUDE_ACKS else 5
        ops = chain(width)
        try:
            conn.request("POST", "/docs/crash/ops",
                         body=json_codec.dumps(Batch(tuple(ops))))
            resp = conn.getresponse()
            out = json.loads(resp.read())
        except Exception:
            # the armed site killed the server mid-request in some
            # OTHER thread's timing — only reachable if os._exit lost
            # a race to this read; nothing was acked
            break
        if resp.status != 200 or not out.get("accepted"):
            break
        for op in ops:
            ack_f.write(op.value + "\n")
        ack_f.flush()
        acked += 1
        if acked == PRELUDE_ACKS:
            # everything above is acked AND fsynced; the next wide
            # commit must die at the armed site
            os.environ["GRAFT_CRASH_POINT"] = SITE
            os.environ["GRAFT_CRASH_EXIT"] = "1"
    print("NOCRASH", flush=True)   # the site never fired: test fails
    os._exit(7)


if __name__ == "__main__":
    main()
