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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.embedding import sharded as E
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


@pytest.mark.parametrize("rows,batch,dim,dtype", [
    (20_512_829, 65536, 16, jnp.bfloat16),    # largest DLRM-50 shard
    (4_000_000, 4096, 128, jnp.float32),      # rows-major orientation
])
def test_lookup_backward_compiles_for_v5e(one_chip, rows, batch, dim, dtype):
    """The lookup's backward (13 slots of 16 per sample): the sorted row
    sums are the Pallas kernel, no scatter is left under the lookup's
    transpose, and the (R, D) gradient is written in place, never
    copied."""
    k, pool = 13, 16
    arena = jax.ShapeDtypeStruct((rows, dim), dtype, sharding=one_chip)
    bases = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((batch, k, pool), jnp.int32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((batch, k, dim), jnp.float32, sharding=one_chip)

    def backward(a, b, i, g):
        return jax.vjp(lambda a: E._local_lookup(a, b, i), a)[1](g)[0]

    compiled = jax.jit(backward).lower(arena, bases, idx, g).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    scatters = [line for line in hlo.splitlines()
                if re.search(r"\bscatter\(", line) and E.LOOKUP_SCOPE in line]
    assert not scatters, scatters[:2]
    # temporaries: the fetched f32 rows and four int32 slot arrays; a copy
    # of the (R, D) gradient would add rows * dim * itemsize more
    slots = batch * k * pool
    budget = slots * dim * 4 + 4 * slots * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= budget
