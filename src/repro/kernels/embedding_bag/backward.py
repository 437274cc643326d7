"""Pallas TPU kernel: the embedding backward's row sums over sorted slots.

The gradient of a pooled lookup with respect to its arena is a sum, for
each arena row, of the pooled gradients of every slot that looked the row
up.  ``repro.embedding.sharded`` sorts the slots by arena row and puts
their gradients in that order; this kernel adds them up, one dense row of
the result per arena row, in f32, and writes each row once.

Design:
  * inputs: ``keys`` (N,) int32 arena rows in ascending order (slots that
    add nothing carry a key >= ``n_rows``), and ``grads``, each slot's f32
    gradient in the same order.  N is a multiple of ``CHUNK``.
  * grid = (cdiv(n_rows, BLOCK_ROWS),): one step per block of
    ``BLOCK_ROWS`` arena rows.  A block's slots are one contiguous range
    ``[lo, hi)`` of the sorted order, found by a binary search of the
    block starts before the kernel and prefetched into SMEM.
  * each step walks its range in ``CHUNK``-slot chunks, DMA'd from HBM
    with the next chunk in flight, and adds each 128-slot row of a chunk
    on the MXU as ``grads (D, 128) @ onehot (128, BLOCK_ROWS)``, where
    ``onehot[c, l]`` is one where slot ``c``'s key is the block's row
    ``l``.  Duplicate rows add inside the matmul, so a hot row costs one
    matmul column per slot, never a serial chain.
  * f32 on the MXU: the one-hot is exact in bf16, and the gradients are
    split into three bf16 parts (hi, mid, lo) that hold all 24 bits of an
    f32 mantissa; the three partial products add in f32.  The block is
    rounded once to the arena's dtype as it is written.  Blocks with no
    slot write zeros.
  * orientation: for D < 128 the kernel writes the (D, n_rows) transpose,
    which is how XLA lays out an (n_rows, 16) array on a TPU, so the
    caller's ``.T`` costs nothing there (and rows of fewer than 128 lanes
    could not be DMA'd); for D >= 128 it writes rows
    (``onehot^T @ grads``).

A non-finite gradient in a chunk spreads to every row of its block (it
multiplies the one-hot's zeros), where a scatter-add would keep it in its
own row; a step that makes one is lost either way.

``sorted_row_sum_ref`` is the plain-JAX form of the same sums (a sorted
``segment_sum`` in f32), run where there is no TPU.  Checked against it in
interpret mode (the CPU tests) and compiled for a described v5e
(``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 2048       # arena rows per grid step
CHUNK = 1024            # slots per DMA: one (8, 128) tile of int32 keys
_LANES = 128


def _lanes_major(dim: int) -> bool:
    """Whether the kernel writes the (D, n_rows) transpose at this D."""
    return dim < _LANES


def _split3(g):
    """f32 -> three bf16 parts whose sum is ``g`` to f32 precision."""
    hi = g.astype(jnp.bfloat16)
    r = g - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _row_sum_kernel(bounds_ref, keys_hbm, grads_hbm, out_ref,
                    kbuf, gbuf, acc, sem, *, n_rows: int, by_lanes: bool,
                    block_rows: int):
    i = pl.program_id(0)
    lo, hi = bounds_ref[i], bounds_ref[i + 1]
    first = lo // CHUNK
    n_chunks = jnp.where(hi > lo, (hi - 1) // CHUNK - first + 1, 0)
    start = i * block_rows
    key_rows = CHUNK // _LANES

    def copies(c, slot):
        at = pl.multiple_of(c * CHUNK, CHUNK)
        g_src = (grads_hbm.at[:, pl.ds(at, CHUNK)] if by_lanes
                 else grads_hbm.at[pl.ds(at, CHUNK), :])
        return (pltpu.make_async_copy(
                    keys_hbm.at[pl.ds(pl.multiple_of(c * key_rows, key_rows),
                                      key_rows)],
                    kbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(g_src, gbuf.at[slot], sem.at[1, slot]))

    @pl.when(n_chunks > 0)
    def _():
        for cp in copies(first, 0):
            cp.start()

    acc[...] = jnp.zeros(acc.shape, acc.dtype)
    rows_iota = jax.lax.broadcasted_iota(jnp.int32, (block_rows, _LANES),
                                         0)
    sub_iota = jax.lax.broadcasted_iota(jnp.int32, (key_rows, _LANES), 0)

    def step(j, carry):
        slot = j % 2

        @pl.when(j + 1 < n_chunks)
        def _():
            for cp in copies(first + j + 1, 1 - slot):
                cp.start()

        for cp in copies(0, slot):
            cp.wait()
        base = (first + j) * CHUNK
        all_keys = kbuf[slot]                                     # (8, 128)

        def add_row(r, c):
            """Adds the chunk's ``r``-th row of 128 slots."""
            keys = jnp.sum(jnp.where(sub_iota == r, all_keys, 0), axis=0,
                           keepdims=True)                         # (1, 128)
            col = jnp.where(keys < n_rows, keys - start, -1)
            onehot = (rows_iota == col).astype(jnp.bfloat16)      # (L, 128)
            at = pl.multiple_of(r * _LANES, _LANES)
            if by_lanes:
                g = gbuf[slot, :, pl.ds(at, _LANES)]              # (D, 128)
                parts = jnp.concatenate(_split3(g), axis=0)       # (3D, 128)
                p = jax.lax.dot_general(
                    parts, onehot, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)           # (3D, L)
                d = g.shape[0]
                acc[...] += p[:d] + p[d:2 * d] + p[2 * d:]
            else:
                g = gbuf[slot, pl.ds(at, _LANES), :]              # (128, D)
                parts = jnp.concatenate(_split3(g), axis=1)       # (128, 3D)
                p = jnp.dot(onehot, parts,
                            preferred_element_type=jnp.float32)   # (L, 3D)
                d = g.shape[1]
                acc[...] += p[:, :d] + p[:, d:2 * d] + p[:, 2 * d:]
            return c

        # only the rows that hold some of the block's slots [lo, hi)
        first_row = jnp.clip((lo - base) // _LANES, 0, key_rows)
        end_row = jnp.clip(pl.cdiv(hi - base, _LANES), 0, key_rows)
        jax.lax.fori_loop(first_row, end_row, add_row, 0)
        return carry

    jax.lax.fori_loop(0, n_chunks, step, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def _block_bounds(keys: jax.Array, n_rows: int,
                  block_rows: int = BLOCK_ROWS) -> jax.Array:
    """(n_blocks + 1,) int32: where each block of ``block_rows`` arena rows
    starts in the sorted ``keys``; the last entry ends the live slots."""
    starts = jnp.minimum(jnp.arange(pl.cdiv(n_rows, block_rows) + 1,
                                    dtype=jnp.int32) * block_rows, n_rows)
    return jnp.searchsorted(keys, starts, side="left",
                            method="scan").astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_rows", "dtype", "interpret",
                                             "block_rows"))
def sorted_row_sum(keys: jax.Array, grads: jax.Array, *, n_rows: int,
                   dtype, interpret: bool = False,
                   block_rows: int = BLOCK_ROWS) -> jax.Array:
    """keys: (N,) int32 ascending; grads: (N, D) f32 in the same order ->
    (n_rows, D) ``dtype``, row r the sum of the grads whose key is r.
    N is a multiple of ``CHUNK``; keys >= ``n_rows`` add nothing.
    ``block_rows`` (a multiple of 128): rows per grid step; fewer suit
    keys that are dense in ``[0, n_rows)``, as compact row ids are."""
    n, dim = grads.shape
    assert n % CHUNK == 0, f"pad the slots to a multiple of {CHUNK}"
    by_lanes = _lanes_major(dim)
    n_blocks = pl.cdiv(n_rows, block_rows)
    if by_lanes:
        g_in, g_buf = grads.T, (2, dim, CHUNK)
        out_shape, out_block = (dim, n_rows), (dim, block_rows)
        out_map = lambda i, bounds: (0, i)                       # noqa: E731
    else:
        g_in, g_buf = grads, (2, CHUNK, dim)
        out_shape, out_block = (n_rows, dim), (block_rows, dim)
        out_map = lambda i, bounds: (i, 0)                       # noqa: E731
    out = pl.pallas_call(
        functools.partial(_row_sum_kernel, n_rows=n_rows, by_lanes=by_lanes,
                          block_rows=block_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(out_block, out_map),
            scratch_shapes=[
                pltpu.VMEM((2, CHUNK // _LANES, _LANES), jnp.int32),
                pltpu.VMEM(g_buf, jnp.float32),
                pltpu.VMEM(out_block, jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(_block_bounds(keys, n_rows, block_rows), keys.reshape(-1, _LANES),
      g_in)
    return out.T if by_lanes else out


def sorted_row_sum_ref(keys: jax.Array, grads: jax.Array, *, n_rows: int,
                       dtype) -> jax.Array:
    """Plain-JAX form of ``sorted_row_sum``: the same sorted sums in f32."""
    return jax.ops.segment_sum(grads.astype(jnp.float32), keys,
                               num_segments=n_rows,
                               indices_are_sorted=True).astype(dtype)
