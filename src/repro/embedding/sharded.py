"""Table-wise model-parallel embedding bags with all-to-all redistribution.

Implements the DLRM distributed embedding pattern of paper App. A.1 in
JAX: tables live on model-axis shards (grouped by a ``PlacementPlan``,
i.e. by DreamShard's placement), each shard performs fused lookups for its
tables over its data-parallel batch slice, and a ``jax.lax.all_to_all``
over the model axis swaps batch-for-tables so the dense (data-parallel)
part of the model sees every table's pooled embedding for its batch rows --
the forward all-to-all of the paper; the transpose in the backward pass is
the backward all-to-all.

Inside the ``shard_map`` the lookup is XLA's gather plus a masked pooled
sum, on every backend; the Pallas forward kernel
(``repro.kernels.embedding_bag.kernel``) is not on this path.

Its backward is a custom VJP that keeps only the indices.  It sorts the
shard's slots by arena row, with padded slots sent past the last row
(``BWD_SORT_SCOPE``); puts each slot's pooled gradient into that order in
f32 (``BWD_FETCH_SCOPE``); and adds each row's gradients in f32 and
writes the dense (R, D) arena gradient in one pass, rounded once to the
arena's dtype (``BWD_ACCUMULATE_SCOPE``).  On a TPU the sums are the
Pallas kernel ``repro.kernels.embedding_bag.backward``; elsewhere its
plain-JAX form, a sorted ``segment_sum``.  So padding costs no update and
a hot row's sum does not stall at bf16's precision.

The lookup runs under the named scope ``LOOKUP_SCOPE`` and the exchange
under ``EXCHANGE_SCOPE``, so a device profile names their ops (and their
transposes: the backward's three steps, under ``transpose(jvp(...))``, and
the backward exchange).  Scopes are metadata only: the compiled program is
the same without them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.embedding.plan import PlacementPlan
from repro.kernels.embedding_bag import backward as bwd_kernel

LOOKUP_SCOPE = "emb.lookup"
EXCHANGE_SCOPE = "emb.exchange"
BWD_SORT_SCOPE = "emb.bwd.sort"
BWD_FETCH_SCOPE = "emb.bwd.fetch"
BWD_ACCUMULATE_SCOPE = "emb.bwd.accumulate"
BWD_SCOPES = (BWD_SORT_SCOPE, BWD_FETCH_SCOPE, BWD_ACCUMULATE_SCOPE)


def init_arenas(key, plan: PlacementPlan, dtype=jnp.float32,
                scale: float = 0.01):
    """(n_shards, rows_max, dim) stacked per-shard arenas.  Row 0 of each
    is reserved: padded slots point at it, and the lookup masks them."""
    arenas = jax.random.normal(
        key, (plan.n_shards, plan.rows_max, plan.dim)) * scale
    return arenas.astype(dtype)


def group_indices(plan: PlacementPlan, indices: np.ndarray) -> np.ndarray:
    """(B, M, P) per-table rows (-1 pad) -> (B, S*K, P) grouped by shard."""
    order = plan.grouped_index_order()
    B, _, Pp = indices.shape
    out = np.full((B, order.shape[0], Pp), -1, indices.dtype)
    live = order >= 0
    out[:, live] = indices[:, order[live]]
    return out


def _local_lookup(arena, bases, idx):
    """arena: (R, D); bases: (K,); idx: (B, K, P) -> (B, K, D) f32.

    Padded slots (-1) add nothing whatever arena row 0 holds, and get no
    gradient, so training leaves row 0 zero."""
    with jax.named_scope(LOOKUP_SCOPE):
        return _lookup(arena.shape, arena.dtype, arena, bases, idx)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lookup(shape, dtype, arena, bases, idx):
    live = idx >= 0
    rows = jnp.take(arena, jnp.where(live, idx + bases[None, :, None], 0),
                    axis=0)                                # (B, K, P, D)
    return jnp.where(live[..., None], rows, 0).astype(jnp.float32).sum(2)


def _lookup_fwd(shape, dtype, arena, bases, idx):
    return _lookup(shape, dtype, arena, bases, idx), (bases, idx)


def _lookup_bwd(shape, dtype, res, g):
    """Sorted f32 row sums of the pooled gradients ``g`` (B, K, D)."""
    bases, idx = res
    n_rows, dim = shape
    bag_slots = idx.shape[-1]
    with jax.named_scope(BWD_SORT_SCOPE):
        keys = jnp.where(idx >= 0, idx + bases[None, :, None],
                         n_rows).reshape(-1).astype(jnp.int32)
        n = keys.shape[0]
        bags = jnp.arange(n, dtype=jnp.int32) // bag_slots
        pad = -n % bwd_kernel.CHUNK
        if pad:
            keys = jnp.concatenate([keys, jnp.full((pad,), n_rows, jnp.int32)])
            bags = jnp.concatenate([bags, jnp.zeros((pad,), jnp.int32)])
        keys, bags = jax.lax.sort((keys, bags), num_keys=1, is_stable=False)
    with jax.named_scope(BWD_FETCH_SCOPE):
        # indexed by (sample, slot) in ``g`` as it arrives: from a flat
        # (B * K, D) copy, which it keeps in VMEM, the TPU compiler takes
        # about two minutes over the same gather
        k = g.shape[1]
        grads = g.astype(jnp.float32)[bags // k, bags % k]      # (N, D)
    with jax.named_scope(BWD_ACCUMULATE_SCOPE):
        sums = functools.partial(bwd_kernel.sorted_row_sum, n_rows=n_rows,
                                 dtype=dtype)
        plain = functools.partial(bwd_kernel.sorted_row_sum_ref,
                                  n_rows=n_rows, dtype=dtype)
        d_arena = jax.lax.platform_dependent(keys, grads, tpu=sums,
                                             default=plain)
    return d_arena, None, None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def make_sharded_lookup(mesh, plan: PlacementPlan, *,
                        data_axes=("data",), model_axis="model"):
    """Build the shard_mapped distributed lookup.

    fn(arenas (S, R, D), indices (B, S*K, P)) ->
        (B, S*K, D) pooled embeddings, batch sharded over
        (data_axes + model) -- i.e. each device ends with its batch
        sub-slice of EVERY table (post all-to-all), the layout the
        data-parallel dense net consumes.
    """
    S = plan.n_shards
    batch_spec = data_axes if len(data_axes) > 1 else data_axes[0]

    def local_fn(arenas, bases, indices):
        # block shapes: arenas (1, R, D); indices (B_loc, K, P)
        arena = arenas[0]
        idx = indices.reshape(indices.shape[0], S, plan.k_max,
                              indices.shape[-1])
        # this shard's group only (its position along model axis)
        m = jax.lax.axis_index(model_axis)
        own = jax.lax.dynamic_index_in_dim(idx, m, axis=1, keepdims=False)
        out = _local_lookup(arena, bases[0], own)      # (B_loc, K, D)
        with jax.named_scope(EXCHANGE_SCOPE):
            # forward all-to-all: trade batch rows for table groups
            out = jax.lax.all_to_all(
                out.reshape(S, out.shape[0] // S, plan.k_max, plan.dim),
                model_axis, split_axis=0, concat_axis=0, tiled=False)
            # (S, B_loc/S, K, D) -> (B_loc/S, S*K, D)
            return jnp.moveaxis(out, 0, 1).reshape(
                out.shape[1], S * plan.k_max, plan.dim)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(model_axis, None, None), P(model_axis, None),
                  P(batch_spec, None, None)),
        out_specs=P((*data_axes, model_axis), None, None),
        check_vma=False)


def combine_shard_outputs(plan: PlacementPlan, grouped):
    """Assemble per-slot pooled outputs into per-table embeddings.

    ``grouped`` is ``(B, S*K, D)`` -- the layout ``make_sharded_lookup``
    / ``lookup_unsharded`` produce, one slot per (device, k) cell.  For
    a whole-table plan each live slot IS its table; for a column-sharded
    plan a slot carries its shard's pooled columns in ``[0, width)`` and
    they scatter into the owner's ``[col_start, col_end)`` range (shards
    tile the owner's columns, so the scatter is a disjoint union).
    Returns ``(B, M, D)`` indexed by table id -- slot bookkeeping
    resolved, the layout a dense net consumes regardless of K.
    """
    order = plan.grouped_index_order()
    out = jnp.zeros((grouped.shape[0], plan.n_tables, plan.dim),
                    grouped.dtype)
    cols = None if plan.slot_cols is None else plan.slot_cols.reshape(-1, 2)
    for s in np.flatnonzero(order >= 0):
        t = int(order[s])
        if cols is None:
            out = out.at[:, t, :].set(grouped[:, s, :])
        else:
            c0, c1 = int(cols[s, 0]), int(cols[s, 1])
            out = out.at[:, t, c0:c1].set(grouped[:, s, :c1 - c0])
    return out


def lookup_unsharded(arenas, bases, indices, plan: PlacementPlan):
    """Single-device oracle with identical semantics (tests/CPU examples)."""
    outs = []
    for s in range(plan.n_shards):
        idx = indices[:, s * plan.k_max:(s + 1) * plan.k_max]
        outs.append(_local_lookup(arenas[s], jnp.asarray(bases[s]), idx))
    return jnp.concatenate(outs, axis=1)               # (B, S*K, D)
