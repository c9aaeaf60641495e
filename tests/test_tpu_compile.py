"""The pallas kernels of the merge path, compiled for a described v5e at
1M rows (ahead-of-time, with no chip attached), and the ops-axis sharded body
compiled for a described v5e:2x2 mesh: what the TPU compiler would
refuse on the chip fails here, at no chip time.  Nothing runs; each
kernel test asserts the kernel's ``tpu_custom_call`` in the compiled
text, the mesh test the collectives of the sharded body.

The topology is described inside a module-scoped fixture, never at
import: one process at a time may load the TPU library, and under
pytest-xdist every worker imports this file.  Keep these tests in this
one file, so that one worker loads it.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from crdt_graph_tpu.ops import fused_resolve, mono_gather, tour_scan

ROWS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A described-chip compile written to the persistent cache cannot
    be read back without the chip (it warns and recompiles): keep the
    cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _assert_kernel(text: str, name: str) -> None:
    pat = re.compile(r"%" + re.escape(name) +
                     r"(\.\d+)? = .*custom_call_target=\"tpu_custom_call\"")
    assert any(pat.search(ln) for ln in text.splitlines()), \
        f"no tpu_custom_call for {name}"


def test_monotone_gather_compiles(one_chip):
    v = jax.ShapeDtypeStruct((7, ROWS), jnp.int32, sharding=one_chip)
    rid = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda a, b: mono_gather.monotone_gather(a, b, use_pallas=True),
        v, rid)
    _assert_kernel(text, mono_gather.KERNEL_NAME)


# node-frame plane widths (ops/merge._finish): D + 4 columns on the
# single-hop sweep, D + 5 on the 2-hop one, for the codec's depth
# buckets D ∈ {1, 2, 4, 8, 16} (codec/packed._depth_bucket, max_depth 16)
DEPTHS = (1, 2, 4, 8, 16)


@pytest.mark.parametrize("cols", [d + 4 for d in DEPTHS])
def test_plane_rows_compiles(one_chip, cols):
    plane = jax.ShapeDtypeStruct((ROWS, cols), jnp.int64,
                                 sharding=one_chip)
    idx = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    with jax.enable_x64(True):
        text = _compiled_text(
            lambda p, i: fused_resolve.plane_rows(p, i, use_pallas=True),
            plane, idx)
    _assert_kernel(text, fused_resolve.KERNEL_NAME)


@pytest.mark.parametrize("cols", [d + 5 for d in DEPTHS])
def test_plane_rows2_compiles(one_chip, cols):
    plane = jax.ShapeDtypeStruct((ROWS, cols), jnp.int64,
                                 sharding=one_chip)
    idx = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    with jax.enable_x64(True):
        text = _compiled_text(
            lambda p, i: fused_resolve.plane_rows2(p, i, 4,
                                                   use_pallas=True),
            plane, idx)
    _assert_kernel(text, fused_resolve.KERNEL_NAME2)


@pytest.mark.parametrize("x64", [False, True])
def test_prefix_sums_compiles(one_chip, x64):
    """Under a global x64 config too: the merge kernel traces it so."""
    b = jax.ShapeDtypeStruct((2 * ROWS,), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((2, ROWS), jnp.int32, sharding=one_chip)
    with jax.enable_x64(x64):
        text = _compiled_text(
            lambda x, y: tour_scan.prefix_sums(x, y, use_pallas=True),
            b, w)
    _assert_kernel(text, tour_scan.KERNEL_NAME)


# the four-chip cell's executable: the ops-axis shard_map body over a
# 4-wide mesh at a small op count (its structure does not depend on M)
OPSAXIS_OPS = 1 << 12


@pytest.mark.parametrize("no_deletes", [True, False])
def test_opsaxis_body_compiles_on_2x2_mesh(topo, no_deletes):
    from crdt_graph_tpu.bench import workloads
    from crdt_graph_tpu.parallel import opsaxis
    k = 4
    mesh = Mesh(np.asarray(topo.devices).reshape(k), (opsaxis.AXIS,))
    arrs = workloads.chain_workload(16, OPSAXIS_OPS) if no_deletes \
        else workloads.chain_with_deletes(OPSAXIS_OPS, 8)
    n = -(-arrs["kind"].shape[0] // k) * k
    cols = opsaxis._cols_of(arrs)
    shapes = {
        c: jax.ShapeDtypeStruct(
            (n,) + arrs[c].shape[1:], arrs[c].dtype,
            sharding=NamedSharding(mesh, P(opsaxis.AXIS) if arrs[c].ndim
                                   == 1 else P(opsaxis.AXIS, None)))
        for c in cols}
    with jax.enable_x64(True):
        text = opsaxis._opsaxis_materialize.lower(
            shapes, mesh, cols, "exhaustive", no_deletes
        ).compile().as_text()
    assert "all-gather" in text and "collective-permute" in text
