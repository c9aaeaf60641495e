"""Multi-PROCESS distribution smoke test (SURVEY §5 A8; VERDICT r2 task 5).

Two real OS processes, each with 4 virtual CPU devices, joined by
``jax.distributed.initialize`` into one 8-device runtime; the docs mesh
axis spans both processes and each feeds only its local documents through
``parallel.distributed.host_local_docs_to_global``.  This exercises the
actual multi-controller code path (process_count == 2), not the
single-process no-op fallbacks.
"""
import os
import socket
import subprocess
import sys
import threading

import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "_distributed_worker.py")
_FLEET = os.path.join(os.path.dirname(__file__), "_fleet_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_fleet_merge():
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(port), str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    except subprocess.TimeoutExpired:
        # kill BOTH workers, then drain their pipes so the hung worker's
        # output (distributed-init barrier logs) makes it into the failure
        for p in procs:
            p.kill()
        drained = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                out = "<unreadable>"
            drained.append(f"--- worker rc={p.returncode} ---\n{out}")
        pytest.fail("distributed workers timed out:\n" + "\n".join(drained))
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out}"
    assert "worker 0: OK" in outs[0]
    assert "worker 1: OK" in outs[1]


def _spawn_fleet(phase, coord_port, http_port, pid, ckpt_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    # fast failure detection: this harness WANTS the injected death
    # observed promptly — the default detector (10 s × 10 misses)
    # would stall the surviving gang member ~100 s per phase, which
    # was most of this test's wall time (tier-1 budget satellite)
    env["GRAFT_DIST_HEARTBEAT_S"] = "1"
    env["GRAFT_DIST_MAX_MISSING"] = "4"
    return subprocess.Popen(
        [sys.executable, _FLEET, phase, str(coord_port), str(http_port),
         str(pid), ckpt_dir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)


def _join(procs, timeout=420):
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        drained = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                out = "<unreadable>"
            drained.append(f"--- rc={p.returncode} ---\n{out}")
        pytest.fail("fleet workers timed out:\n" + "\n".join(drained))
    return outs


def test_fleet_kill_restart_rejoin(tmp_path):
    """VERDICT r4 next-8: kill one process of a running compute fleet
    mid-session, detect the death (exit code — the controller's failure
    detector), restart, and rejoin via snapshot + /ops?since= to full
    convergence; then a fresh gang re-forms from the replicated state
    alone.  The data plane is the replication service (the fleet's
    durable truth, reference recovery semantics CRDTree.elm:408-418);
    the compute plane is jax.distributed whose collectives are
    gang-scheduled — mid-collective death means gang restart, which the
    refleet phase models."""
    from crdt_graph_tpu.service import make_server

    srv = make_server(port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        # phase 1: both fleet workers run; worker 1 dies mid-session
        coord = _free_port()
        procs = [_spawn_fleet("run", coord, srv.server_port, pid,
                              str(tmp_path)) for pid in (0, 1)]
        outs = _join(procs)
        assert procs[1].returncode == 17, outs[1]     # died as injected
        # the survivor finishes its WORK (merge verified, edits pushed)
        # but cannot cleanly outlive the gang: jax's coordination
        # service detects the dead peer by heartbeat timeout and fails
        # the shutdown barrier — the runtime's own failure detector,
        # observable to the controller alongside the exit codes
        assert "fleet merge pre-crash OK" in outs[0]
        assert "worker 0: OK" in outs[0]
        assert procs[0].returncode == 0 \
            or "heartbeat timeout" in outs[0] \
            or "Shutdown barrier" in outs[0], outs[0]
        assert "worker 1: dying mid-session" in outs[1]
        doc = srv.store.get("fleet", create=False)
        # server holds worker 0's 40 edits + worker 1's pushed half only
        assert len(doc.tree.visible_values()) == 60
        assert os.path.exists(str(tmp_path / "w1.npz"))

        # phase 2: controller detected rc=17; replacement rejoins
        rec = _spawn_fleet("rejoin", 0, srv.server_port, 1,
                           str(tmp_path))
        out = _join([rec])[0]
        assert rec.returncode == 0, out
        assert "rejoined: OK" in out
        assert len(doc.tree.visible_values()) == 80
        assert doc.metrics()["dup_absorbed"] >= 60    # idempotent re-push

        # phase 3: a brand-new gang re-forms purely from the service
        coord2 = _free_port()
        procs = [_spawn_fleet("refleet", coord2, srv.server_port, pid,
                              str(tmp_path)) for pid in (0, 1)]
        outs = _join(procs)
        for p, out in zip(procs, outs):
            assert p.returncode == 0, out
        assert "fleet merge post-restart OK" in outs[0]
    finally:
        srv.shutdown()
        srv.server_close()
