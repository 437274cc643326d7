"""Pallas TPU kernel: fused multi-table pooled embedding lookup.

TPU adaptation of the FBGEMM fused embedding-bag (paper's hot-spot op).
The GPU idiom (one warp per row, warp-shuffle reductions) has no TPU
analogue; the transferable insight is *fusion*: all tables of one device
are stacked into a single height-padded arena so ONE kernel launch serves
every (sample, table) lookup, amortizing launch overhead exactly like the
fused op the paper models (App. A.3.2).

Design:
  * arena: (rows, dim_padded) -- all tables vertically stacked; row 0 is a
    reserved zero row that padded pooling slots point at.  It stays in HBM
    (``pl.ANY``); rows are fetched by manual DMA.
  * indices: (n_bags, pool) int32 arena-row ids, one bag per
    (sample, table) pair, already offset by table base row.  They are
    blocked per tile of ``TILE_BAGS`` bags into SMEM, so SMEM holds one
    tile's ids whatever the batch size.
  * grid = (n_bags / TILE_BAGS,).  Per tile, pooling slot ``p`` DMAs one
    row per bag into a (TILE_BAGS, dim) VMEM buffer while slot ``p - 1``
    is summed into the f32 output tile (two buffers, so VMEM use does not
    grow with the pooling factor).
  * HBM tiles rows in groups (8 for 32-bit, pairs packed into one 32-bit
    sublane for bf16), and a DMA may not cut a tile's sublane.  The arena
    is therefore viewed as (rows / pack, pack, dim) -- a free bitcast for
    dim = 128 -- so each DMA moves whole 32-bit sublanes: one row for
    32-bit arenas, the packed row pair for bf16, whose wanted half is
    picked by the row's parity.

Checked against ``ref.py`` in interpret mode (the CPU tests), compiled for
a described v5e chip (``tests/test_tpu_compile.py``), and run compiled on a
TPU v5e chip at a DLRM-50 shard shape by ``chip_smoke.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_BAGS = 128


def _bag_kernel(idx_ref, *refs, pool: int):
    """Pool one tile of bags: ``out[b] = sum_p arena[idx[b, p]]``."""
    *par, src, out_ref, buf, sem = refs
    tile, dim = out_ref.shape
    pack = src.shape[1]

    def copy(b, row, slot):
        return pltpu.make_async_copy(src.at[pl.ds(row // pack, 1)],
                                     buf.at[slot, pl.ds(b, 1)], sem.at[slot])

    def start(p, slot):
        def body(b, c):
            copy(b, idx_ref[b * pool + p], slot).start()
            return c
        jax.lax.fori_loop(0, tile, body, 0)

    def wait(slot):
        def body(b, c):
            copy(0, 0, slot).wait()
            return c
        jax.lax.fori_loop(0, tile, body, 0)

    start(0, 0)
    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def step(p, c):
        slot = p % 2

        @pl.when(p + 1 < pool)
        def _():
            start(p + 1, 1 - slot)

        wait(slot)
        if pack == 1:
            rows = buf[slot].reshape(tile, dim).astype(jnp.float32)
        else:                       # bf16 pair in one 32-bit word per lane
            words = pltpu.bitcast(buf[slot], jnp.uint32).reshape(tile, dim)
            lane = jax.lax.broadcasted_iota(jnp.int32, par[0].shape, 1)
            odd = jnp.sum(jnp.where(lane == p, par[0][...], 0), axis=1,
                          keepdims=True)
            rows = pltpu.bitcast(
                jnp.where(odd == 1, words & jnp.uint32(0xFFFF0000),
                          words << 16), jnp.float32)
        out_ref[...] += rows
        return c

    jax.lax.fori_loop(0, pool, step, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_fused(arena: jax.Array, indices: jax.Array,
                        *, interpret: bool = True) -> jax.Array:
    """Pooled-sum lookup. arena: (R, D128), indices: (N, P) -> (N, D128).

    Padded pooling slots must point at row 0 (zero row).  Arenas are
    32-bit or bf16.
    """
    n_bags, pool = indices.shape
    rows, dim = arena.shape
    assert dim % 128 == 0, "pad dim to a 128-lane multiple (ops.py does this)"
    pack = 4 // arena.dtype.itemsize
    assert pack in (1, 2), f"unsupported arena dtype {arena.dtype}"
    if rows % pack:
        arena = jnp.pad(arena, ((0, pack - rows % pack), (0, 0)))
    n_pad = pl.cdiv(n_bags, TILE_BAGS) * TILE_BAGS
    idx = jnp.pad(indices, ((0, n_pad - n_bags), (0, 0)))

    operands = [idx.reshape(-1)]
    in_specs = [pl.BlockSpec((TILE_BAGS * pool,), lambda i: (i,),
                             memory_space=pltpu.SMEM)]
    if pack == 2:                   # row parity picks the half of a pair
        operands.append(idx % 2)
        in_specs.append(pl.BlockSpec((TILE_BAGS, pool), lambda i: (i, 0)))
    operands.append(arena.reshape(-1, pack, dim))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    out = pl.pallas_call(
        functools.partial(_bag_kernel, pool=pool),
        grid=(n_pad // TILE_BAGS,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((TILE_BAGS, dim), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, dim), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, TILE_BAGS, pack, dim), arena.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(*operands)
    return out[:n_bags]
