"""The work a placed DLRM train step requires, whatever implements it.

Counted from the configuration's shapes and the batch's own indices,
never from a compiled program, so that a later sparse update, dedup or
kernel does not make the count stale.

FLOPs (2 per multiply-add):

- every MLP layer: forward ``2 B n_in n_out``, weight gradient the same,
  input gradient the same except for the bottom MLP's first layer, whose
  input is data;
- the dot interaction over the ``n (n - 1) / 2`` pairs of the ``n =
  tables + 1`` features: ``2 B D`` per pair forward, twice that backward.

HBM bytes:

- embedding forward: one read of each live looked-up row, one write of
  the pooled outputs;
- embedding backward and row-wise update: one read of the pooled
  outputs' gradients, one read and one write of each distinct touched
  row and of its accumulator entry;
- the step: both, plus the dense side's read of the pooled outputs and
  write of their gradients.

Padded slots and untouched arena rows count for nothing.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    emb_fwd_bytes: float
    emb_bwd_bytes: float
    step_bytes: float


def mlp_flops(batch: int, sizes: dict) -> float:
    total = 0.0
    for name, widths in sizes.items():
        for i, (n_in, n_out) in enumerate(zip(widths[:-1], widths[1:])):
            passes = 2 if (name == "bottom" and i == 0) else 3
            total += passes * 2.0 * batch * n_in * n_out
    return total


def interaction_flops(batch: int, n_tables: int, dim: int) -> float:
    n = n_tables + 1
    return 3 * 2.0 * batch * dim * n * (n - 1) / 2


def step_work(batch: int, n_tables: int, dim: int, sizes: dict,
              live_lookups: int, distinct_rows: int, row_bytes: int,
              acc_bytes: int, pooled_bytes: int) -> Work:
    """``live_lookups``: looked-up rows in the batch (padding excluded);
    ``distinct_rows``: distinct (shard, row) pairs among them;
    ``row_bytes``: bytes of one arena element; ``acc_bytes``: of one
    accumulator entry; ``pooled_bytes``: of one pooled output element."""
    pooled = float(batch) * n_tables * dim * pooled_bytes
    rows_read = float(live_lookups) * dim * row_bytes
    touched = float(distinct_rows) * 2 * (dim * row_bytes + acc_bytes)
    fwd = rows_read + pooled
    bwd = pooled + touched
    return Work(flops=mlp_flops(batch, sizes)
                + interaction_flops(batch, n_tables, dim),
                emb_fwd_bytes=fwd, emb_bwd_bytes=bwd,
                step_bytes=fwd + bwd + 2 * pooled)


def count_lookups(gidx: np.ndarray, k_max: int, base_rows: np.ndarray):
    """(live lookups, distinct (shard, row) pairs) of one (B, S*K, P)
    index array, on the host (tests and small batches)."""
    live = gidx >= 0
    shard = np.arange(gidx.shape[1]) // k_max
    rows = gidx + base_rows.reshape(-1)[None, :, None]
    keys = shard[None, :, None].astype(np.int64) * (1 << 40) + rows
    return int(live.sum()), int(np.unique(keys[live]).size)


def device_count_fn(k_max: int, base_rows: np.ndarray, rows_max: int):
    """jitted ``gidx -> (live lookups, distinct rows)`` on the device,
    one shard at a time (sort, then count the changes)."""
    import jax
    import jax.numpy as jnp
    n_shards = base_rows.shape[0]

    def count(gidx):
        live_total = jnp.sum(gidx >= 0)
        distinct = jnp.zeros((), jnp.int32)
        for s in range(n_shards):
            idx = gidx[:, s * k_max:(s + 1) * k_max]
            rows = jnp.where(idx >= 0, idx + jnp.asarray(
                base_rows[s], jnp.int32)[None, :, None], rows_max)
            srt = jnp.sort(rows.reshape(-1))
            new = jnp.concatenate([srt[:1] < rows_max,
                                   (srt[1:] != srt[:-1]) & (srt[1:]
                                                            < rows_max)])
            distinct = distinct + jnp.sum(new)
        return live_total, distinct

    return jax.jit(count)
