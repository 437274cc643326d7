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
(``repro.kernels.embedding_bag.kernel``) is not on this path.  A
``(B, K, P)`` shard lookup gathers only the index columns (slot k,
position p) that hold a live id somewhere in the batch: a bag padded to
P leaves the same trailing columns padding in every sample.  It finds
the live columns on the device and runs one loop over them
(``COLUMNS_SCOPE``): each column one gather of B rows, masked and added
in f32 into its slot's bag.  The loop is traced once, so the program
does not grow with K or P; the telemetry counter
``COLUMN_LOOP_COUNTER`` counts the shard lookups traced this way.  An
arena narrower than the 128 lanes, whose width D divides them, is
gathered by row group: the lookup first regroups it into a lane-dense
``(ceil(R / g), 128)`` view, ``g = 128 // D`` rows a group
(``REGROUP_SCOPE``; on a TPU the Pallas kernel
``repro.kernels.embedding_bag.regroup``, elsewhere a pad and reshape),
since a TPU lays an (R, D) arena out as its (D, R) transpose and a
gather of single rows reads one lane column at a time.  It gathers each
slot's group, keeps the D lanes of the slot's own row (and of live slots
only), sums each bag in f32 and then folds the g lane groups to D.  The
other rows' lanes add exact zeros, so a bag sums the per-row gather's
f32 terms (in another order); the telemetry counter
``LANE_GROUPED_COUNTER`` counts the shard lookups traced this way.  At
D = 128, or a D that does not divide 128, the lookup gathers single
rows.

Its backward is a custom VJP that keeps only the indices.  It sorts the
shard's slots by arena row, with padded slots sent past the last row
(``BWD_SORT_SCOPE``); puts each slot's pooled gradient into that order in
f32 (``BWD_FETCH_SCOPE``); and adds each row's gradients in f32 and
writes the dense (R, D) arena gradient in one pass, rounded once to the
arena's dtype (``BWD_ACCUMULATE_SCOPE``).  On a TPU the sums are the
Pallas kernel ``repro.kernels.embedding_bag.backward``; elsewhere its
plain-JAX form, a sorted ``segment_sum``.  So padding costs no update and
a hot row's sum does not stall at bf16's precision.

A plan with bag widths (``PlacementPlan.col_slot``) gives each shard
``(B, W)`` index columns, each column a static slot; the lookup gathers
``(B, W, D)`` and sums each slot's columns, and the backward's bag of
column ``c`` of sample ``b`` is ``b * K + col_slot[c]``.

``rowwise_adagrad_rows`` is the embedding update that keeps no dense
gradient: from the pooled gradients it sorts the shard's slots and
fetches their gradients as the backward does, then (``UPDATE_ROWS_SCOPE``)
sums each touched row's in f32 into a compact array, adds the row's mean
squared gradient to its f32 accumulator, and writes the new values of
the touched rows alone, in place.

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

from repro import telemetry
from repro.embedding.plan import PlacementPlan
from repro.kernels.embedding_bag import backward as bwd_kernel
from repro.kernels.embedding_bag import regroup as regroup_kernel
from repro.kernels.embedding_bag import row_update as row_kernel

LOOKUP_SCOPE = "emb.lookup"
REGROUP_SCOPE = "emb.lookup.regroup"
LANE_GROUPED_COUNTER = "emb.lookup.lane_grouped"
COLUMNS_SCOPE = "emb.lookup.columns"
COLUMN_LOOP_COUNTER = "emb.lookup.column_loop"
EXCHANGE_SCOPE = "emb.exchange"
BWD_SORT_SCOPE = "emb.bwd.sort"
BWD_FETCH_SCOPE = "emb.bwd.fetch"
BWD_ACCUMULATE_SCOPE = "emb.bwd.accumulate"
BWD_SCOPES = (BWD_SORT_SCOPE, BWD_FETCH_SCOPE, BWD_ACCUMULATE_SCOPE)
UPDATE_ROWS_SCOPE = "emb.update.rows"
COMPACT_BLOCK_ROWS = 256    # compact ids are dense: small row-sum blocks


def init_arenas(key, plan: PlacementPlan, dtype=jnp.float32,
                scale: float = 0.01):
    """(n_shards, rows_max, dim) stacked per-shard arenas.  Row 0 of each
    is reserved: padded slots point at it, and the lookup masks them."""
    arenas = jax.random.normal(
        key, (plan.n_shards, plan.rows_max, plan.dim)) * scale
    return arenas.astype(dtype)


def group_indices(plan: PlacementPlan, indices: np.ndarray) -> np.ndarray:
    """(B, M, P) per-table rows (-1 pad) -> (B, S*K, P) grouped by shard;
    for a plan with bag widths, -> (B, S*W): each slot's table's first
    ``width`` ids in the slot's columns (P must cover the widest)."""
    if plan.col_slot is not None:
        B = indices.shape[0]
        out = np.full((B, plan.n_shards, plan.n_cols), -1, indices.dtype)
        for s in range(plan.n_shards):
            for k, (c0, c1) in enumerate(plan.col_ranges(s)):
                if c1 > c0:
                    t = plan.slot_table[s, k]
                    out[:, s, c0:c1] = indices[:, t, :c1 - c0]
        return out.reshape(B, -1)
    order = plan.grouped_index_order()
    B, _, Pp = indices.shape
    out = np.full((B, order.shape[0], Pp), -1, indices.dtype)
    live = order >= 0
    out[:, live] = indices[:, order[live]]
    return out


def _local_lookup(arena, bases, idx, col_slot=None):
    """arena: (R, D); bases: (K,); idx: (B, K, P) -> (B, K, D) f32; or,
    with ``col_slot`` (W,) static slot per column (-1 = padding), idx
    (B, W) -> (B, K, D) f32.

    Padded slots (-1) add nothing whatever arena row 0 holds, and get no
    gradient, so training leaves row 0 zero."""
    if _lane_group(arena.shape[1]) > 1:
        telemetry.count(LANE_GROUPED_COUNTER)
    with jax.named_scope(LOOKUP_SCOPE):
        if col_slot is None:
            telemetry.count(COLUMN_LOOP_COUNTER)
            return _lookup(arena.shape, arena.dtype, arena, bases, idx)
        return _lookup_cols(arena.shape, arena.dtype, tuple(
            int(k) for k in col_slot), arena, bases, idx)


def _lane_group(dim: int) -> int:
    """Arena rows to one 128-lane row group: ``128 // dim`` where ``dim``
    is narrower than the lanes and divides them, else 1 (single rows)."""
    lanes = regroup_kernel.LANES
    return lanes // dim if dim < lanes and lanes % dim == 0 else 1


def _row_groups(arena):
    """(R, D) -> the lane-dense (ceil(R / g), 128) view of the arena."""
    with jax.named_scope(REGROUP_SCOPE):
        return jax.lax.platform_dependent(
            arena, tpu=lambda a: regroup_kernel.row_groups(a.T),
            default=regroup_kernel.row_groups_ref)


def _view(arena):
    """What the lookup gathers from: a narrow arena's (``_lane_group``)
    lane-dense row groups, else the arena itself."""
    if _lane_group(arena.shape[1]) == 1:
        return arena
    return _row_groups(arena)


def _take(view, keys, dim):
    """``_view``'s rows at ``keys`` (...): (..., D); for a narrow arena
    each key's 128-lane row group, (..., 128)."""
    g = view.shape[1] // dim
    return jnp.take(view, keys // g if g > 1 else keys, axis=0)


def _mask(rows, keys, live, dim):
    """``_take``'s rows in f32, zero where not ``live``, and in a row
    group every lane but the key's own row's zero.  Row groups stay
    (..., 128) until ``_fold``, after pooling: reshaping every gathered
    row to (g, D) would be a relayout of all of them."""
    g = rows.shape[-1] // dim
    live = live[..., None]
    if g > 1:
        live = live & (jnp.arange(rows.shape[-1], dtype=keys.dtype) // dim
                       == (keys % g)[..., None])
    return jnp.where(live, rows, 0).astype(jnp.float32)


def _fold(pooled, dim):
    """(..., 128) pooled row groups -> (..., dim): the lane groups
    summed; (..., dim) unchanged."""
    g = pooled.shape[-1] // dim
    if g == 1:
        return pooled
    return pooled.reshape(*pooled.shape[:-1], g, dim).sum(-2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _lookup(shape, dtype, arena, bases, idx):
    batch, k_slots, pool = idx.shape
    dim = shape[1]
    view = _view(arena)
    cols = idx.reshape(batch, k_slots * pool).T    # (K * P, B)
    live = jnp.any(cols >= 0, axis=1)
    order = jnp.argsort(~live, stable=True)        # live columns first

    def add_column(j, bags):
        c = jax.lax.dynamic_index_in_dim(order, j, keepdims=False)
        k = c // pool
        ids = jax.lax.dynamic_index_in_dim(cols, c, keepdims=False)
        ok = ids >= 0
        keys = jnp.where(
            ok, ids + jax.lax.dynamic_index_in_dim(bases, k, keepdims=False),
            0)
        rows = _mask(_take(view, keys, dim), keys, ok, dim)   # (B, D or 128)
        bag = jax.lax.dynamic_index_in_dim(bags, k, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(bags, bag + rows, k, 0)

    with jax.named_scope(COLUMNS_SCOPE):
        bags = jax.lax.fori_loop(
            0, jnp.sum(live, dtype=jnp.int32), add_column,
            jnp.zeros((k_slots, batch, view.shape[1]), jnp.float32))
    # laid out for the loop: without the barrier XLA lays the bags out
    # batch-minor, as the (B, K, D) result wants, and transposes a slot's
    # (B, 128) block in and out in every iteration
    bags = jax.lax.optimization_barrier(bags)
    return jnp.moveaxis(_fold(bags, dim), 0, 1)


def _lookup_fwd(shape, dtype, arena, bases, idx):
    return _lookup(shape, dtype, arena, bases, idx), (bases, idx)


def _sort_slots(keys, bags, n_rows):
    """Pads the slots to a multiple of the kernel's chunk (padding keyed
    past the last row) and sorts them by arena row."""
    pad = -keys.shape[0] % bwd_kernel.CHUNK
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), n_rows, jnp.int32)])
        bags = jnp.concatenate([bags, jnp.zeros((pad,), jnp.int32)])
    return jax.lax.sort((keys, bags), num_keys=1, is_stable=False)


def _fetch(g, bags):
    """The f32 pooled gradient of each sorted slot's bag, ``g`` (B, K, D).

    Indexed by (sample, slot) in ``g`` as it arrives: from a flat
    (B * K, D) copy, which it keeps in VMEM, the TPU compiler takes about
    two minutes over the same gather."""
    with jax.named_scope(BWD_FETCH_SCOPE):
        k = g.shape[1]
        return g.astype(jnp.float32)[bags // k, bags % k]       # (N, D)


def _sorted_slots(bases, idx, n_rows):
    """(keys, bags) of a (B, K, P) shard's slots, sorted by arena row."""
    with jax.named_scope(BWD_SORT_SCOPE):
        keys = jnp.where(idx >= 0, idx + bases[None, :, None],
                         n_rows).reshape(-1).astype(jnp.int32)
        bags = jnp.arange(keys.shape[0], dtype=jnp.int32) // idx.shape[-1]
        return _sort_slots(keys, bags, n_rows)


def _lookup_bwd(shape, dtype, res, g):
    """Sorted f32 row sums of the pooled gradients ``g`` (B, K, D)."""
    bases, idx = res
    keys, bags = _sorted_slots(bases, idx, shape[0])
    return _accumulate(keys, _fetch(g, bags), shape[0], dtype), None, None


def _accumulate(keys, grads, n_rows, dtype):
    """The dense (n_rows, D) gradient from sorted keys and their grads."""
    with jax.named_scope(BWD_ACCUMULATE_SCOPE):
        sums = functools.partial(bwd_kernel.sorted_row_sum, n_rows=n_rows,
                                 dtype=dtype)
        plain = functools.partial(bwd_kernel.sorted_row_sum_ref,
                                  n_rows=n_rows, dtype=dtype)
        return jax.lax.platform_dependent(keys, grads, tpu=sums,
                                          default=plain)


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def _col_keys(bases, idx, col_slot, n_rows):
    """Arena row of each (sample, column), ``n_rows`` for padding."""
    slot = np.maximum(np.asarray(col_slot), 0)
    return jnp.where(idx >= 0, idx + bases[slot][None, :], n_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _lookup_cols(shape, dtype, col_slot, arena, bases, idx):
    keys = _col_keys(bases, idx, col_slot, 0)
    rows = _take(_view(arena), keys, shape[1])     # (B, W, D or 128)
    rows = _mask(rows, keys, idx >= 0, shape[1])
    return _fold(_pool_cols(rows, col_slot, bases.shape[0]), shape[1])


def _pool_cols(rows, col_slot, k_slots):
    """(B, W, D) -> (B, K, D): each slot's static run of columns summed."""
    col_slot = np.asarray(col_slot)
    out = []
    for k in range(k_slots):
        cols = np.flatnonzero(col_slot == k)
        if cols.size:
            out.append(rows[:, cols[0]:cols[-1] + 1].sum(1))
        else:
            out.append(jnp.zeros((rows.shape[0], rows.shape[2]), rows.dtype))
    return jnp.stack(out, axis=1)


def _lookup_cols_fwd(shape, dtype, col_slot, arena, bases, idx):
    return (_lookup_cols(shape, dtype, col_slot, arena, bases, idx),
            (bases, idx))


def _sorted_col_slots(bases, idx, col_slot, n_rows, k_slots):
    """(keys, bags) of a column-layout shard's slots, sorted by row; the
    bag of column ``c`` of sample ``b`` is ``b * K + col_slot[c]``."""
    with jax.named_scope(BWD_SORT_SCOPE):
        keys = _col_keys(bases, idx, col_slot, n_rows).reshape(-1)
        slot = np.maximum(np.asarray(col_slot), 0).astype(np.int32)
        bags = (jnp.arange(idx.shape[0], dtype=jnp.int32)[:, None] * k_slots
                + jnp.asarray(slot)[None, :]).reshape(-1)
        return _sort_slots(keys.astype(jnp.int32), bags, n_rows)


def _lookup_cols_bwd(shape, dtype, col_slot, res, g):
    bases, idx = res
    keys, bags = _sorted_col_slots(bases, idx, col_slot, shape[0],
                                   g.shape[1])
    return _accumulate(keys, _fetch(g, bags), shape[0], dtype), None, None


_lookup_cols.defvjp(_lookup_cols_fwd, _lookup_cols_bwd)


def _compact_row_sums(keys, grads, n):
    """Sum of the f32 grads of each run of equal sorted keys: (n, D)."""
    sums = functools.partial(bwd_kernel.sorted_row_sum, n_rows=n,
                             dtype=jnp.float32,
                             block_rows=COMPACT_BLOCK_ROWS)
    plain = functools.partial(bwd_kernel.sorted_row_sum_ref, n_rows=n,
                              dtype=jnp.float32)
    return jax.lax.platform_dependent(keys, grads, tpu=sums, default=plain)


def _dense_acc_sums(rows, sq, n_rows):
    """(n_rows,) f32: ``sq`` added at its sorted, unique ``rows``."""
    sums = functools.partial(bwd_kernel.sorted_row_sum, n_rows=n_rows,
                             dtype=jnp.float32)
    plain = functools.partial(bwd_kernel.sorted_row_sum_ref, n_rows=n_rows,
                              dtype=jnp.float32)
    return jax.lax.platform_dependent(rows, sq[:, None], tpu=sums,
                                      default=plain)[:, 0]


def _write_touched(arena, rows, new, n):
    """``arena`` with ``arena[rows[j]] = new[j]`` for ``j < n`` (rows
    sorted and unique, the rest ``n_rows``), written in place.

    On a TPU one DMA a row (``kernels/embedding_bag/row_update``); a DMA
    moves whole 32-bit sublanes, so a bf16 row goes out with its pair
    partner, whose value is its new one where it is touched too (the
    next or previous entry) and its old one otherwise."""
    n_rows, dim = arena.shape
    pack = 4 // arena.dtype.itemsize

    def kernel(arena, rows, new, n):
        at = jnp.minimum(rows, n_rows - 1)
        if pack == 1:
            return row_kernel.write_rows(arena[:, None], at, new[:, None],
                                         n)[:, 0]
        odd = (rows & 1) == 1
        nxt = jnp.concatenate([rows[1:], rows[-1:]])
        prv = jnp.concatenate([rows[:1], rows[:-1]])
        other = jnp.where(
            (~odd & (nxt == rows + 1))[:, None], jnp.roll(new, -1, 0),
            jnp.where((odd & (prv == rows - 1))[:, None], jnp.roll(new, 1, 0),
                      arena[jnp.minimum(at ^ 1, n_rows - 1)]))
        pair = jnp.where(odd[:, None, None], jnp.stack([other, new], 1),
                         jnp.stack([new, other], 1))
        return row_kernel.write_rows(arena.reshape(n_rows // 2, 2, dim),
                                     at >> 1, pair, n).reshape(n_rows, dim)

    def plain(arena, rows, new, n):
        return arena.at[rows].set(new, mode="drop")

    return jax.lax.platform_dependent(arena, rows, new, n, tpu=kernel,
                                      default=plain)


def rowwise_adagrad_rows(arena, acc, bases, idx, g, *, lr, eps,
                         col_slot=None):
    """Row-wise Adagrad on the rows one shard's lookups touched.

    arena (R, D); acc (R,) f32; bases (K,); idx (B, K, P), or (B, W) with
    ``col_slot``; g (B, K, D) the pooled lookups' gradients.  Each
    touched row r gets ``acc[r] += mean(g_r ** 2)`` and ``arena[r] -= lr
    * g_r / (sqrt(acc[r]) + eps)``, g_r the f32 sum of its slots'
    gradients, rounded once to the arena's dtype.  The arena is read and
    written at the touched rows alone (on a TPU a bf16 row goes with its
    pair partner, written back unchanged where untouched); the (R,) f32
    accumulators take one dense add.  No dense (R, D) gradient exists.
    Returns (arena, acc)."""
    n_rows, dim = arena.shape
    if 4 // arena.dtype.itemsize == 2 and n_rows % 2:
        raise ValueError("a bf16 arena updated by rows needs an even row "
                         "count (build_plan(..., pad_rows_to=2))")
    if col_slot is None:
        keys, bags = _sorted_slots(bases, idx, n_rows)
    else:
        keys, bags = _sorted_col_slots(bases, idx, tuple(col_slot), n_rows,
                                       g.shape[1])
    grads = _fetch(g, bags)
    with jax.named_scope(UPDATE_ROWS_SCOPE):
        n = keys.shape[0]
        live = keys < n_rows
        first = live & (keys != jnp.concatenate(
            [jnp.full((1,), -1, jnp.int32), keys[:-1]]))
        compact = jnp.cumsum(first.astype(jnp.int32)) - 1
        sums = _compact_row_sums(jnp.where(live, compact, n), grads, n)
        rows = jnp.sort(jnp.where(first, keys, n_rows))
        touched = jnp.sum(first.astype(jnp.int32))
        sq = jnp.mean(sums * sums, axis=-1)
        acc = acc + _dense_acc_sums(rows, sq, n_rows)
        at = jnp.minimum(rows, n_rows - 1)
        scale = lr / (jnp.sqrt(acc[at]) + eps)
        new = (arena[at].astype(jnp.float32)
               - sums * scale[:, None]).astype(arena.dtype)
        return _write_touched(arena, rows, new, touched), acc


def make_sharded_lookup(mesh, plan: PlacementPlan, *,
                        data_axes=("data",), model_axis="model"):
    """Build the shard_mapped distributed lookup.

    fn(arenas (S, R, D), indices (B, S*K, P)) ->
        (B, S*K, D) pooled embeddings, batch sharded over
        (data_axes + model) -- i.e. each device ends with its batch
        sub-slice of EVERY table (post all-to-all), the layout the
        data-parallel dense net consumes.
    """
    if plan.col_slot is not None:
        raise NotImplementedError(
            "the sharded lookup takes plans without bag widths; a column "
            "layout runs on one device (lookup_unsharded)")
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

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(model_axis, None, None), P(model_axis, None),
                  P(batch_spec, None, None)),
        out_specs=P((*data_axes, model_axis), None, None),
        check_vma=False)
    fn.is_sharded = True        # make_train_step refuses it a row update
    return fn


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


def shard_indices(plan: PlacementPlan, indices, s: int):
    """Shard ``s``'s part of the grouped indices: (B, K, P), or (B, W)
    for a plan with bag widths."""
    if plan.col_slot is None:
        return indices[:, s * plan.k_max:(s + 1) * plan.k_max]
    return indices[:, s * plan.n_cols:(s + 1) * plan.n_cols]


def lookup_unsharded(arenas, bases, indices, plan: PlacementPlan):
    """Single-device oracle with identical semantics (tests/CPU examples);
    the lookup of a plan with bag widths."""
    outs = []
    for s in range(plan.n_shards):
        idx = shard_indices(plan, indices, s)
        cols = None if plan.col_slot is None else plan.col_slot[s]
        outs.append(_local_lookup(arenas[s], jnp.asarray(bases[s]), idx,
                                  cols))
    return jnp.concatenate(outs, axis=1)               # (B, S*K, D)
