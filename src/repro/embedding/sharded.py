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
sum, on every backend.  The Pallas kernel (``repro.kernels.embedding_bag``)
is not on this path; which of the two the step should use is open until
both are measured on the chip.

The lookup runs under the named scope ``LOOKUP_SCOPE`` and the exchange
under ``EXCHANGE_SCOPE``, so a device profile names their ops (and their
transposes, the backward scatter-add and exchange).  Scopes are metadata
only: the compiled program is the same without them.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.embedding.plan import PlacementPlan

LOOKUP_SCOPE = "emb.lookup"
EXCHANGE_SCOPE = "emb.exchange"


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

    Padded slots (-1) add nothing whatever arena row 0 holds; the mask
    also keeps their gradient off row 0, so training leaves it zero."""
    with jax.named_scope(LOOKUP_SCOPE):
        live = idx >= 0
        rows = jnp.take(arena,
                        jnp.where(live, idx + bases[None, :, None], 0),
                        axis=0)                            # (B, K, P, D)
        return jnp.where(live[..., None], rows, 0).astype(
            jnp.float32).sum(2)


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
