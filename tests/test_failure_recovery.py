"""Failure injection over the replication service (SURVEY §5 failure
semantics; VERDICT r3's distributed row lacked any failure test).

A real worker PROCESS joins the service, edits, pushes half its log,
checkpoints its full local state, and dies hard (os._exit mid-session).
The parent detects the failure (exit code), observes the partial server
state, then restarts the worker from its checkpoint — recovery is pure
CRDT anti-entropy: pull absorbs the overlap as duplicates, the re-push
is idempotent, and both sides converge on the full edit history.  No
coordination, fencing, or replay log beyond the checkpoint is needed —
that is the failure model the semilattice join buys."""
import os
import subprocess
import sys
import threading

from crdt_graph_tpu.service import make_server

_WORKER = os.path.join(os.path.dirname(__file__), "_crash_worker.py")


def _run_worker(phase, port, ckpt):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, _WORKER, phase, str(port), ckpt],
        env=env, capture_output=True, text=True, timeout=300)


def test_worker_crash_checkpoint_resync(tmp_path):
    srv = make_server(port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    ckpt = str(tmp_path / "wal.npz")
    try:
        # phase 1: worker dies mid-session with half its log unpushed
        crashed = _run_worker("crash", srv.server_port, ckpt)
        assert crashed.returncode == 3, crashed.stdout + crashed.stderr
        doc = srv.store.get("wal", create=False)
        assert doc is not None
        assert len(doc.tree.visible_values()) == 5   # the pushed half
        assert os.path.exists(ckpt)                  # the local WAL

        # phase 2: restart from the checkpoint; anti-entropy converges
        rec = _run_worker("recover", srv.server_port, ckpt)
        assert rec.returncode == 0, rec.stdout + rec.stderr
        assert "recovered: OK" in rec.stdout
        assert doc.tree.visible_values() == \
            [f"edit-{i}" for i in range(10)]
        # the overlap was absorbed, not re-applied
        assert doc.metrics()["dup_absorbed"] >= 5
    finally:
        srv.shutdown()
        srv.server_close()
