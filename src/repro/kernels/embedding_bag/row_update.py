"""Pallas TPU kernel: write rows of a table in place, at given row ids.

The row-wise Adagrad update on touched rows
(``repro.embedding.sharded.rowwise_adagrad_rows``) computes each touched
arena row's new value in a compact array and has to put it back where
the row lives, leaving every other row as it is.  XLA's scatter does that
one update after another; this kernel issues one DMA per row instead,
from the compact rows in VMEM to the table in HBM, which it aliases as
its output, so that nothing else of the table is read or written.

Design:
  * ``table`` (T, pack, D) in HBM (``pl.ANY``), aliased to the output.
    HBM tiles rows in groups and a DMA may not cut a 32-bit sublane, so
    a bf16 arena is viewed as row pairs (``pack`` 2, a free bitcast at
    D = 128) and the caller hands in whole pairs; a 32-bit table is
    viewed with ``pack`` 1.
  * ``idx`` (N,) int32 units of ``table``, blocked per ``CHUNK_ROWS`` into
    SMEM; ``rows`` (N, pack, D) in the same order, blocked into VMEM;
    ``n`` (prefetched scalar): only the first ``n`` entries are written.
  * grid = (N / CHUNK_ROWS,).  A step starts one DMA per live entry of
    its chunk, then waits for them all, so the chunk's writes overlap.
    Entries that name the same unit must carry the same value (the
    caller gives both rows of a touched pair the pair's new value).

Checked in interpret mode against an indexed update (the CPU tests) and
compiled for a described v5e (``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK_ROWS = 1024       # entries per grid step: one SMEM block of ids,
                        # as XLA tiles a 1-D int32 array


def _write_kernel(n_ref, idx_ref, rows_ref, table_in, table_out, sem):
    del table_in                    # aliased to ``table_out``
    live = jnp.clip(n_ref[0] - pl.program_id(0) * CHUNK_ROWS, 0, CHUNK_ROWS)

    def copy(j, unit):
        return pltpu.make_async_copy(rows_ref.at[pl.ds(j, 1)],
                                     table_out.at[pl.ds(unit, 1)], sem.at[0])

    def start(j, c):
        copy(j, idx_ref[j]).start()
        return c

    def wait(j, c):
        copy(0, 0).wait()
        return c

    jax.lax.fori_loop(0, live, start, 0)
    jax.lax.fori_loop(0, live, wait, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_rows(table: jax.Array, idx: jax.Array, rows: jax.Array,
               n: jax.Array, *, interpret: bool = False) -> jax.Array:
    """table (T, pack, D); idx (N,) int32; rows (N, pack, D) of the
    table's dtype; n: a scalar.  Returns the table with ``table[idx[j]] =
    rows[j]`` for ``j < n``, written in place where the table is
    donated.  N is a multiple of ``CHUNK_ROWS``."""
    n_entries = idx.shape[0]
    assert n_entries % CHUNK_ROWS == 0, \
        f"pad the entries to a multiple of {CHUNK_ROWS}"
    block = (CHUNK_ROWS, *rows.shape[1:])
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_entries // CHUNK_ROWS,),
            in_specs=[pl.BlockSpec((CHUNK_ROWS,), lambda i, n: (i,),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec(block, lambda i, n: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(n, (1,)).astype(jnp.int32), idx, rows, table)
