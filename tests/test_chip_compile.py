"""The main path's kernels compiled for a described TPU v5e.

Nothing runs: the TPU compiler, installed here, compiles for a 2x2 v5e
topology that is described, not attached. That catches what interpret mode
and the CPU backend cannot — a tiling or VMEM refusal, a kernel that does
not fit the device — before it costs chip time. The topology is described
inside a fixture (only the worker that runs this file loads the TPU
library), and the persistent compile cache is off around these compiles:
an entry compiled for a described chip cannot be read back here.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

PALLAS_LANES = 2048  # two 1,024-lane blocks
SHA_LANES = 65536  # the largest bucket (bench.BUCKETS)
MESH_LANES = 16384  # 4,096 per device


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _words(n, sharding):
    return jax.ShapeDtypeStruct((8, n), jnp.uint32, sharding=sharding)


def test_pallas_verify_kernel_compiles_for_v5e(one_chip):
    """Two blocks and the live count: the scalar prefetch, the skip of
    dead blocks and their clamped input blocks all pass Mosaic."""
    from corda_tpu.ops import ed25519_pallas

    w = _words(PALLAS_LANES, one_chip)
    live = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = ed25519_pallas.verify_arrays_pallas.lower(
        w, w, w, w, live).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel
    mem = compiled.memory_analysis()
    # The words, and the int32 live count in one 512-byte buffer.
    assert mem.argument_size_in_bytes == 4 * 8 * PALLAS_LANES * 4 + 512
    assert mem.temp_size_in_bytes < 1 << 20  # everything lives in VMEM


def test_sha512_challenge_compiles_at_the_largest_bucket(one_chip):
    from corda_tpu.ops import sha512_jax

    w = _words(SHA_LANES, one_chip)
    compiled = sha512_jax.challenge_words.lower(w, w, w).compile()
    assert compiled.out_info.shape == (8, SHA_LANES)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_sharded_verify_compiles_over_a_four_chip_mesh(topo,
                                                       no_persistent_cache):
    from corda_tpu.ops import sharded

    mesh = Mesh(np.array(topo.devices), (sharded.BATCH_AXIS,))
    assert mesh.devices.size == 4
    w = _words(MESH_LANES, NamedSharding(mesh, P(None, sharded.BATCH_AXIS)))
    compiled = sharded.sharded_verify_hashed_fn(mesh).lower(
        w, w, w, w).compile()
    text = compiled.as_text()
    # Per-lane independent: no collective on the verify path.
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute"):
        assert op not in text, op
    # Arguments are per device: each chip holds its 4,096-lane slice.
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 4 * 8 * (MESH_LANES // 4) * 4
    out = compiled.output_shardings
    assert out.spec == P(sharded.BATCH_AXIS)
