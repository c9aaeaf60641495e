"""Test harness configuration.

JAX tests run on a simulated 8-device CPU mesh so multi-chip sharding is
exercised without TPU hardware; the chip is reached only through
``python chip_smoke.py`` (and ``--chips 4``) run by the chip tool.

Tests stay on the CPU by two means, both needed: ``JAX_PLATFORMS=cpu``
in the env (set by crdt_graph_tpu.utils.hostenv, shared with
__graft_entry__, loaded here by file path so nothing else of the
package imports first) and ``jax.config.update("jax_platforms", "cpu")``
right after ``import jax``, before any backend initialises.
"""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "_hostenv",
    os.path.join(os.path.dirname(__file__), "..", "crdt_graph_tpu",
                 "utils", "hostenv.py"))
_hostenv = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_hostenv)
_hostenv.scrub_tpu_env(8)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Arm the engine's vouch tripwire for the whole suite: every batch that
# reaches the kernel's cond-free exhaustive mode is re-audited on host
# first (engine._mode), so a producer bug breaking the hint-completeness
# invariant fails a test loudly instead of silently mis-resolving.
os.environ.setdefault("GRAFT_DEBUG_VOUCH", "1")


# -- shared HTTP-service fixtures (test_service, test_elm_interop) --------

import json as _json                   # noqa: E402
import threading as _threading         # noqa: E402
from http.client import HTTPConnection as _HTTPConnection  # noqa: E402

import pytest as _pytest               # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (-m 'not slow'); run ad hoc "
        "for acceptance-scale workloads (e.g. the 1M-op serving soak)")


@_pytest.fixture(autouse=True)
def _reset_process_wide_observability():
    """The span registry and the default flight recorder are
    process-wide (by design — they are the production post-mortem
    surface), which made span assertions depend on test ORDER: whichever
    test touched a ServingEngine first left ``serve.*`` spans behind for
    every later assertion.  Reset both after every test so each test
    observes only its own telemetry (ISSUE 5 satellite)."""
    yield
    from crdt_graph_tpu.obs import flight as _flight
    from crdt_graph_tpu.utils import profiling as _profiling
    _profiling.reset_spans()
    _flight.reset_default_recorder()


@_pytest.fixture()
def server():
    from crdt_graph_tpu.service import make_server
    srv = make_server(port=0)
    thread = _threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    yield srv
    srv.shutdown()
    srv.server_close()


@_pytest.fixture()
def req():
    def _req(srv, method, path, body=None):
        conn = _HTTPConnection("127.0.0.1", srv.server_port, timeout=30)
        conn.request(method, path, body=body)
        resp = conn.getresponse()
        payload = _json.loads(resp.read().decode())
        conn.close()
        return resp.status, payload
    return _req
