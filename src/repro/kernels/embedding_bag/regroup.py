"""Pallas TPU kernel: a narrow arena regrouped into 128-lane row groups.

An (R, D) arena with D < 128 is laid out on a TPU as its (D, R)
transpose, so one of its rows is a lane column spread over a whole tile,
and a gather of single rows reads one lane of each.  The lookup
(``repro.embedding.sharded``) gathers from a lane-dense view instead:
``g = 128 // D`` consecutive rows to one 128-lane row,

    view[q, D * j + d] = arena[g * q + j, d],   shape (ceil(R / g), 128),

with the rows past R zero.  This kernel writes that view from the
arena's physical (D, R) form (``arena.T``, which costs nothing there) in
one pass: each grid step reads a (D, ``BLOCK_ROWS``) block, masks the
columns past R, transposes it in VMEM and stores each of the g strided
row sets (rows j, j + g, ...) into its D lanes.  Plain data movement: the
view holds the arena's values, and a non-finite row stays in its own
lanes.

``row_groups_ref`` is the plain-JAX form (pad and reshape), run where
there is no TPU; checked against it in interpret mode (the CPU tests)
and compiled for a described v5e (``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 4096       # arena rows per grid step; 2048 is 1.3 ms a shard
                        # slower on a v5e (20.5 M rows of 16)


def _regroup_kernel(at_ref, out_ref, cols, *, n_rows: int, group: int):
    dim = at_ref.shape[0]
    col = (pl.program_id(0) * BLOCK_ROWS
           + jax.lax.broadcasted_iota(jnp.int32, at_ref.shape, 1))
    cols[...] = jnp.where(col < n_rows, at_ref[...].astype(jnp.float32),
                          0.0).T                       # (BLOCK_ROWS, D)
    for j in range(group):
        out_ref[:, dim * j:dim * (j + 1)] = cols[
            pl.ds(j, BLOCK_ROWS // group, stride=group), :].astype(
                out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def row_groups(arena_t, *, interpret: bool = False):
    """``arena_t`` (D, R), the arena's transpose, D dividing 128 ->
    (ceil(R / g), 128) row groups, g = 128 // D."""
    dim, n_rows = arena_t.shape
    group = LANES // dim
    return pl.pallas_call(
        functools.partial(_regroup_kernel, n_rows=n_rows, group=group),
        grid=(pl.cdiv(n_rows, BLOCK_ROWS),),
        in_specs=[pl.BlockSpec((dim, BLOCK_ROWS), lambda i: (0, i))],
        out_specs=pl.BlockSpec((BLOCK_ROWS // group, LANES),
                               lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pl.cdiv(n_rows, group), LANES),
                                       arena_t.dtype),
        scratch_shapes=[pltpu.VMEM((BLOCK_ROWS, dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(arena_t)


def row_groups_ref(arena):
    """Plain-JAX form of ``row_groups(arena.T)``: (R, D) -> (ceil(R / g),
    128)."""
    n_rows, dim = arena.shape
    group = LANES // dim
    return jnp.pad(arena, ((0, -n_rows % group), (0, 0))).reshape(-1, LANES)
