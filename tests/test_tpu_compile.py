"""The pallas kernels of the merge path, compiled for a described v5e at
1M rows (on-chip-measurement guide §2): what Mosaic would refuse on the
chip fails here, at no chip time.  Nothing runs; each test asserts the
kernel's ``tpu_custom_call`` in the compiled text.

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


@pytest.mark.parametrize("x64", [False, True])
def test_ring_exclusive_pallas_compiles_on_2x2_mesh(topo, x64):
    k = 4
    mesh = Mesh(np.asarray(topo.devices).reshape(k), ("ops",))
    fn = jax.shard_map(
        lambda v: tour_scan.ring_exclusive_pallas(v, k), mesh=mesh,
        in_specs=(P("ops"),), out_specs=P("ops"), check_vma=False)
    vals = jax.ShapeDtypeStruct((k * 8,), jnp.int32,
                                sharding=NamedSharding(mesh, P("ops")))
    with jax.enable_x64(x64):
        text = _compiled_text(fn, vals)
    _assert_kernel(text, tour_scan.RING_KERNEL_NAME)
