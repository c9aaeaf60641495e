"""Crash/recovery worker for tests/test_failure_recovery.py.

Phase "crash": join the service, make 10 local edits, push only the
first half, checkpoint the FULL local state (the WAL role of
checkpoint_packed), then die hard mid-session (os._exit) — the unpushed
tail exists only in the checkpoint.

Phase "recover": restore the checkpoint (own replica id rides in it),
pull the server's log (anti-entropy; everything already pushed comes
back as duplicates and absorbs), re-push the whole local log
(idempotent — the server absorbs the first half again), and verify both
sides converged on all 10 edits.

Usage: python tests/_crash_worker.py PHASE PORT CHECKPOINT_PATH
"""
import json
import os
import sys

PHASE, PORT, CKPT = sys.argv[1], int(sys.argv[2]), sys.argv[3]

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from http.client import HTTPConnection  # noqa: E402

from crdt_graph_tpu import engine  # noqa: E402
from crdt_graph_tpu.codec import json_codec  # noqa: E402


def req(method, path, body=None):
    conn = HTTPConnection("127.0.0.1", PORT, timeout=30)
    conn.request(method, path, body=body)
    resp = conn.getresponse()
    payload = json.loads(resp.read().decode())
    conn.close()
    return resp.status, payload


def crash() -> None:
    _, r = req("POST", "/docs/wal/replicas")
    t = engine.init(r["replica"])
    for i in range(10):
        t.add(f"edit-{i}")
    # push only the first half...
    half_ts = t.operations_since(0).ops[4].ts
    first_half = json_codec.dumps(
        engine.Batch(t.operations_since(0).ops[:5]))
    st, out = req("POST", "/docs/wal/ops", first_half)
    assert st == 200 and out["accepted"], out
    # ...checkpoint everything (the local WAL), then die mid-session
    t.checkpoint_packed(CKPT)
    print(f"crashing with {half_ts} pushed", flush=True)
    os._exit(3)


def recover() -> None:
    t = engine.TpuTree.restore_packed(CKPT)
    assert t.log_length == 10, t.log_length
    # anti-entropy pull: server ops re-apply; the overlap absorbs
    _, ops = req("GET", "/docs/wal/ops?since=0")
    t.apply(json_codec.decode(ops))
    assert t.log_length == 10, t.log_length   # nothing new, all dups
    # idempotent re-push of the whole local log: the server absorbs the
    # five it has and applies the five that died with the first worker
    st, out = req("POST", "/docs/wal/ops",
                  json_codec.dumps(t.operations_since(0)))
    assert st == 200 and out["accepted"], out
    _, snap = req("GET", "/docs/wal")
    assert snap["values"] == t.visible_values() == \
        [f"edit-{i}" for i in range(10)], snap
    print("recovered: OK", flush=True)


if __name__ == "__main__":
    {"crash": crash, "recover": recover}[PHASE]()
