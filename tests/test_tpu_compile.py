"""Compile the main path's kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  This catches what interpret mode cannot -- block
shapes the tiling refuses, SMEM or VMEM overruns -- at the real widths of
the placed DLRM step (128 lanes, batch 65536, pooling 16, multi-million-row
arenas).  The topology is described inside a module fixture, never at
import, so every test worker collects the same tests and only the worker
running this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.embedding_bag.kernel import embedding_bag_fused


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("rows,n_bags,dtype", [
    (20_500_000, 65536 * 13, jnp.bfloat16),   # largest DLRM-50 shard
    (4_000_000, 65536, jnp.float32),
    (1_000_001, 65536, jnp.bfloat16),         # odd rows: one padding row
])
def test_embedding_bag_compiles_for_v5e(one_chip, rows, n_bags, dtype):
    arena = jax.ShapeDtypeStruct((rows, 128), dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((n_bags, 16), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda a, i: embedding_bag_fused(a, i, interpret=False)
    ).lower(arena, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # an arena whose rows fill whole 32-bit sublanes is viewed in place;
    # only an odd bf16 arena is copied, to pad its last pair
    arena_bytes = rows * 128 * jnp.dtype(dtype).itemsize
    copied = compiled.memory_analysis().temp_size_in_bytes >= arena_bytes
    assert copied == (dtype == jnp.bfloat16 and rows % 2 == 1)
