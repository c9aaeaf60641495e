"""``join64_1M_x4.catchup`` at a size a CPU holds, on four virtual
devices with the route's threshold lowered so that every push's merge
is sharded over the ops axis: ``correct`` is true for the program and
false for the control, and false where an exchange of the sharded merge
is left out:

- ``ring``: the ``ppermute`` ring that carries each device's scan
  totals to the next (``tour_scan.ring_exclusive``; on the catch-up
  path at every size);
- ``all_gather``: the tiled ``all_gather`` that reassembles each
  device's rows of a sharded gather or scan (``OpsAxisPart._ag``), so
  that each device sees its own rows and zeros elsewhere.

The ``psum`` of ``OpsAxisPart.frame_add`` is not among them: a chain's
push takes the hinted sibling leg, where it is not traced, and on the
counted leg a chain's answer cannot show it (each device holds at least
two of the root's 64 children, and every other parent has one child,
so every ``cnt >= 2`` test reads the same with the sum or without).

Each run is a process of its own: the device count is fixed when JAX
starts, and a fault has to be traced afresh.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "join64_1M_x4.catchup"
OPS = 64 * 256

SCRIPT = """
import json, sys
sys.path.insert(0, {root!r})
import jax
jax.config.update("jax_platforms", "cpu")
from jax import lax
import jax.numpy as jnp
from crdt_graph_tpu.ops import tour_scan
from crdt_graph_tpu.parallel import opsaxis
fault, control, trace = sys.argv[1], sys.argv[2] == "1", sys.argv[3] == "1"
P = opsaxis.OpsAxisPart


def local_rows(self, local, n):
    w = local.shape[0]
    i = lax.axis_index(opsaxis.AXIS)
    full = jnp.zeros((self.k * w,) + local.shape[1:], local.dtype)
    at = (i * w,) + (jnp.zeros((), i.dtype),) * (local.ndim - 1)
    return lax.dynamic_update_slice(full, local, at)[:n]


hit = []
if fault == "all_gather":
    def spy(self, *a, **kw):
        hit.append(fault)
        return local_rows(self, *a, **kw)
    P._ag = spy
elif fault == "ring":
    def no_ring(vals, axis, k, op="add"):
        hit.append(fault)
        return jnp.zeros_like(vals)
    tour_scan.ring_exclusive = no_ring
from benchmark import harness
r = harness.run({cell!r}, 2**33 + 29, 2.0, trace, control=control,
                allow_cpu=True, overrides={{"ops": {ops}}})
r["routed"] = opsaxis.stats()["merges"]
r["hit"] = len(hit)
print(json.dumps(r, default=str))
"""


def _run(fault, control, trace):
    e = dict(os.environ, JAX_PLATFORMS="cpu",
             XLA_FLAGS="--xla_force_host_platform_device_count=4",
             GRAFT_OPSAXIS="1", GRAFT_OPSAXIS_MIN_OPS=str(OPS // 2))
    code = SCRIPT.format(root=ROOT, cell=CELL, ops=OPS)
    p = subprocess.run([sys.executable, "-c", code, fault,
                        str(int(control)), str(int(trace))],
                       cwd=ROOT, env=e, capture_output=True, text=True,
                       timeout=600)
    return p.returncode, p.stdout, p.stderr


def _result(fault="none", control=False, trace=False):
    rc, out, err = _run(fault, control, trace)
    assert rc == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1])


def test_program_is_correct_and_routes():
    r = _result(trace=True)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["count"] == 4
    assert r["routed"] >= 2
    assert r["metrics"]["opsaxis_row_share.catchup"]["value"] > 0


def test_control_is_not_correct():
    r = _result(control=True)
    assert not r["correct"]
    assert r["checks"]["value_mismatches"]["value"] > 0


@pytest.mark.parametrize("fault", ["ring", "all_gather"])
def test_an_exchange_left_out_is_not_correct(fault):
    """Either the run gives ``correct`` false, or the server refuses a
    warm-up push (a wrong table rejects valid ops) and the run gives no
    result."""
    rc, out, err = _run(fault, False, False)
    if rc:
        assert "warm-up" in err and "accepted" in err, err[-3000:]
        return
    r = json.loads(out.strip().splitlines()[-1])
    assert r["hit"] > 0 and r["routed"] >= 2
    assert not r["correct"], r["checks"]
