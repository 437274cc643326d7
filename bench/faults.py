"""Faults planted under a step cell's timed path, for the check's tests.

Each returns a compiled replacement for ``StepCell.compiled`` (same call,
same state and batch layout), so that a run goes on as usual around it:

- ``unchanged``: the step computes its loss but returns the state it got;
- ``half_batch``: the step sees only the first half of the batch, and
  its mean runs over that half;
- ``no_exchange`` (cells across chips): the lookup leaves out the
  all-to-all, so each chip's batch rows get its own shard's tables and
  nothing of the others';
- ``altered_lookup``: the lookup reads the row after the right one for
  the first table of the first shard.
"""

from __future__ import annotations

FAULTS = ("unchanged", "half_batch", "no_exchange", "altered_lookup")


def applies(fault: str, cell) -> bool:
    return fault != "no_exchange" or cell.prog.mesh is not None


def _jit(cell, fn):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    shard = cell.shard
    loss = jax.tree.leaves(shard[0])[0]
    if cell.prog.mesh is not None:
        loss = NamedSharding(cell.prog.mesh, P())
    return cell.compile(jax.jit(fn, in_shardings=shard,
                                out_shardings=(*shard[:3], loss),
                                donate_argnums=(0, 1, 2)))


def _step(cell, lookup):
    from repro.models.dlrm import make_train_step
    return make_train_step(cell.prog.model, lookup, cell.prog.emb_opt,
                           cell.prog.dense_opt)


def plant(fault: str, cell):
    import jax
    import jax.numpy as jnp
    step = _step(cell, cell.prog.lookup)
    if fault == "unchanged":
        def fn(p, e, d, b):
            return (p, e, d, step(p, e, d, b)[3])
        return _jit(cell, fn)
    if fault == "half_batch":
        def fn(p, e, d, b):
            return step(p, e, d, jax.tree.map(
                lambda x: x[:x.shape[0] // 2], b))
        return _jit(cell, fn)
    if fault == "altered_lookup":
        rows = int(cell.layout.table_rows[cell.layout.slot_table[0, 0]])

        def lookup(a, bases, gidx):
            first = gidx[:, 0]
            first = jnp.where(first >= 0, (first + 1) % rows, first)
            return cell.prog.lookup(a, bases, gidx.at[:, 0].set(first))
        return _jit(cell, _step(cell, lookup))
    if fault == "no_exchange":
        return _jit(cell, _step(cell, _lookup_without_exchange(cell)))
    raise KeyError(fault)


def _lookup_without_exchange(cell):
    """``make_sharded_lookup`` with its all-to-all left out: each chip
    keeps its own shard's pooled rows for its slice of the batch and
    leaves the other shards' slots at zero."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.embedding.sharded import _local_lookup
    plan, mesh = cell.prog.plan, cell.prog.mesh
    S, K = plan.n_shards, plan.k_max

    def local_fn(arenas, bases, indices):
        m = jax.lax.axis_index("model")
        idx = indices.reshape(indices.shape[0], S, K, indices.shape[-1])
        own = jax.lax.dynamic_index_in_dim(idx, m, axis=1, keepdims=False)
        n = indices.shape[0] // S
        own = jax.lax.dynamic_slice_in_dim(own, m * n, n, axis=0)
        out = _local_lookup(arenas[0], bases[0], own)        # (n, K, D)
        full = jnp.zeros((n, S, K, plan.dim), out.dtype)
        full = jax.lax.dynamic_update_index_in_dim(full, out, m, axis=1)
        return full.reshape(n, S * K, plan.dim)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P("model", None, None), P("model", None),
                  P("data", None, None)),
        out_specs=P(("data", "model"), None, None), check_vma=False)
