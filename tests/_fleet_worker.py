"""Worker for the fleet failure-injection test (tests/test_distributed.py
::test_fleet_kill_restart_rejoin; VERDICT r4 next-8).

Three phases model the life of a 2-process compute fleet whose data
plane is the replication service:

- ``run`` (pid 0 and 1): join the jax.distributed runtime, claim a
  replica id from the service, make 40 local edits, checkpoint the full
  local state (the WAL role of ``checkpoint_packed``), push HALF the
  edits, run the 8-doc fleet merge (real collectives across both
  processes), then pid 1 dies hard (``os._exit``) with its second half
  unpushed — death mid-session, after the gang-scheduled collective.
  (A death DURING a collective hangs the gang — XLA collectives are
  all-or-nothing, same as the reference's NCCL world — so the fleet
  policy for that case is detect-and-restart of the whole gang, which
  phase ``refleet`` exercises.)
- ``rejoin`` (single replacement for the dead pid 1): warm restart from
  its WAL checkpoint, anti-entropy pull (the overlap absorbs as
  duplicates), idempotent re-push of its whole log; THEN a total-loss
  observer bootstraps from ``GET /snapshot`` under a fresh replica id
  and catches up over ``/ops?since=<last add it knows>`` (inclusive
  overlap absorbs) — both converge with the server.
- ``refleet`` (pid 0 and 1, fresh coordinator): a NEW gang re-forms
  with NO local state, each process bootstrapping purely from the
  service snapshot, and re-runs the fleet merge — the compute fleet is
  stateless modulo the replicated data plane.

Usage: python tests/_fleet_worker.py PHASE COORD_PORT HTTP_PORT PID CKPT_DIR
"""
import io
import json
import os
import sys

PHASE, COORD_PORT, HTTP_PORT, PID, CKPT_DIR = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    sys.argv[5])

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from http.client import HTTPConnection  # noqa: E402

from crdt_graph_tpu import engine  # noqa: E402
from crdt_graph_tpu.bench import workloads  # noqa: E402
from crdt_graph_tpu.codec import json_codec  # noqa: E402
from crdt_graph_tpu.ops import merge  # noqa: E402
from crdt_graph_tpu.parallel import distributed  # noqa: E402
from crdt_graph_tpu.parallel import mesh as mesh_mod  # noqa: E402

N_PROCS = 2
DOCS_PER_PROC = 4
N_PAD = 64
EDITS = 40
DOC = "fleet"


def req(method, path, body=None, raw=False):
    conn = HTTPConnection("127.0.0.1", HTTP_PORT, timeout=60)
    conn.request(method, path, body=body)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, (data if raw else json.loads(data))


def fleet_merge(tag: str) -> None:
    """The compute-fleet half: 8 documents sharded across both
    processes' devices, merged collectively, fingerprint-checked
    against local single-device merges (mix-up detection) — the same
    recipe as _distributed_worker.py, run here to pin that fleet
    compute and the data-plane session coexist."""
    import jax.numpy as jnp

    def doc_ops(doc_id):
        return mesh_mod._pad_ops_to(
            workloads.chain_workload(2 + doc_id, 30), N_PAD)

    mesh = distributed.global_device_mesh(n_ops=1)
    my_docs = range(PID * DOCS_PER_PROC, (PID + 1) * DOCS_PER_PROC)
    local = [doc_ops(d) for d in my_docs]
    stacked = {k: np.stack([d[k] for d in local]) for k in local[0]}
    # global assembly exercised; compute runs on this process's local
    # shard and convergence is KV-verified — see _distributed_worker.py
    # (this jaxlib's CPU client cannot EXECUTE cross-process
    # computations; a TPU fleet runs the global jit here)
    global_ops = distributed.host_local_docs_to_global(stacked, mesh)
    assert all(not v.is_fully_addressable for v in global_ops.values())
    from jax.sharding import Mesh
    local_mesh = Mesh(
        np.asarray(jax.local_devices()).reshape(DOCS_PER_PROC, 1),
        (mesh_mod.DOCS_AXIS, mesh_mod.OPS_AXIS))
    table = mesh_mod.batched_materialize(stacked, local_mesh)

    def fp(t):
        return jnp.sum(jnp.where(t.visible, t.ts % jnp.int64(1000003), 0),
                       axis=-1)

    fp_l = np.asarray(jax.jit(fp)(table)).tolist()
    got = distributed.allgather_scalars(
        f"fleetfp-{tag}",
        {PID * DOCS_PER_PROC + i: int(v) for i, v in enumerate(fp_l)})
    for d in range(8):
        want = int(np.asarray(jax.device_get(jax.jit(fp)(
            merge.materialize({k: jax.device_put(v)
                               for k, v in doc_ops(d).items()})))))
        assert int(got[d]) == want, (tag, d, int(got[d]), want)
    print(f"worker {PID}: fleet merge {tag} OK", flush=True)


def run() -> None:
    distributed.initialize(f"127.0.0.1:{COORD_PORT}",
                           num_processes=N_PROCS, process_id=PID)
    assert jax.process_count() == N_PROCS
    _, r = req("POST", f"/docs/{DOC}/replicas")
    t = engine.init(r["replica"])
    for i in range(EDITS):
        t.add(f"w{PID}-e{i}")
    # WAL: full local state is durable before anything is pushed
    t.checkpoint_packed(os.path.join(CKPT_DIR, f"w{PID}.npz"))
    half = engine.Batch(t.operations_since(0).ops[:EDITS // 2])
    st, out = req("POST", f"/docs/{DOC}/ops", json_codec.dumps(half))
    assert st == 200 and out["accepted"], out

    fleet_merge("pre-crash")        # collectives run gang-scheduled

    if PID == 1:
        print("worker 1: dying mid-session", flush=True)
        os._exit(17)                # second half exists only in the WAL
    rest = engine.Batch(t.operations_since(0).ops[EDITS // 2:])
    st, out = req("POST", f"/docs/{DOC}/ops", json_codec.dumps(rest))
    assert st == 200 and out["accepted"], out
    print(f"worker {PID}: OK", flush=True)


def rejoin() -> None:
    # warm restart: the WAL checkpoint carries replica id + unpushed tail
    t = engine.TpuTree.restore_packed(os.path.join(CKPT_DIR, "w1.npz"))
    assert t.log_length == EDITS, t.log_length
    # snapshot BEFORE the re-push: the observer below must need /ops?since=
    _, snap = req("GET", f"/docs/{DOC}/snapshot", raw=True)
    # anti-entropy pull + idempotent re-push
    _, ops = req("GET", f"/docs/{DOC}/ops?since=0", raw=True)
    t.apply(json_codec.loads(ops.decode()))
    st, out = req("POST", f"/docs/{DOC}/ops",
                  json_codec.dumps(t.operations_since(0)))
    assert st == 200 and out["accepted"], out

    # total-loss observer: snapshot bootstrap under a FRESH id, then
    # catch up over /ops?since= (inclusive-add semantics: start from the
    # newest add the snapshot contains; the overlap absorbs)
    _, r = req("POST", f"/docs/{DOC}/replicas")
    obs = engine.TpuTree.restore_packed(io.BytesIO(snap),
                                        replica=r["replica"])
    last_known = max(op.ts for op in obs.operations_since(0).ops
                     if isinstance(op, engine.Add))
    _, delta = req("GET", f"/docs/{DOC}/ops?since={last_known}", raw=True)
    obs.apply(json_codec.loads(delta.decode()))

    _, doc = req("GET", f"/docs/{DOC}")
    assert sorted(doc["values"]) == sorted(t.visible_values()) \
        == sorted(obs.visible_values()), "rejoin did not converge"
    assert len(doc["values"]) == N_PROCS * EDITS
    print("rejoined: OK", flush=True)


def refleet() -> None:
    distributed.initialize(f"127.0.0.1:{COORD_PORT}",
                           num_processes=N_PROCS, process_id=PID)
    # gang re-forms with zero local state: bootstrap from the service
    _, snap = req("GET", f"/docs/{DOC}/snapshot", raw=True)
    _, r = req("POST", f"/docs/{DOC}/replicas")
    t = engine.TpuTree.restore_packed(io.BytesIO(snap),
                                      replica=r["replica"])
    assert len(t.visible_values()) == N_PROCS * EDITS
    fleet_merge("post-restart")
    print(f"worker {PID}: refleet OK", flush=True)


if __name__ == "__main__":
    {"run": run, "rejoin": rejoin, "refleet": refleet}[PHASE]()
