"""CPU jax.profiler breakdown of the 1M-op merge (TPU proportions differ
but the op-level structure is shared)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"

import glob
import gzip
import json
from collections import defaultdict

import numpy as np
import jax
import jax.numpy as jnp

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from crdt_graph_tpu.bench.workloads import chain_workload
from crdt_graph_tpu.ops import merge


@jax.jit
def run(o):
    t = merge._materialize(o)
    s = jnp.int64(0)
    for a in (t.doc_index, t.status, t.visible_order):
        s += jnp.sum(a.astype(jnp.int64) % 1000003)
    return s


ops = chain_workload(64, 1_000_000)
dev = jax.device_put(ops)
np.asarray(run(dev))
logdir = "/tmp/cputrace"
os.system(f"rm -rf {logdir}")
jax.profiler.start_trace(logdir)
np.asarray(run(dev))
jax.profiler.stop_trace()

files = glob.glob(logdir + "/**/*.trace.json.gz", recursive=True)
agg = defaultdict(float)
cnt = defaultdict(int)
for f in files:
    with gzip.open(f, "rt") as fh:
        data = json.load(fh)
    for e in data.get("traceEvents", []):
        if e.get("ph") == "X" and "dur" in e and e.get("tid") is not None:
            name = e.get("name", "?")
            if name.startswith(("thread", "process")):
                continue
            agg[name] += e["dur"]
            cnt[name] += 1
total = sum(agg.values())
print(f"total traced: {total/1e3:.1f} ms over {len(agg)} op names")
for name, dur in sorted(agg.items(), key=lambda kv: -kv[1])[:35]:
    print(f"{dur/1e3:9.1f} ms  x{cnt[name]:<4d} {name[:100]}")
