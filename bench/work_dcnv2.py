"""The work a DLRM-DCNv2 train step requires, whatever implements it.

Counted from the configuration's shapes and the batch's own ids, never
from a compiled program, as ``bench.work`` counts the DLRM-50 step.

FLOPs (2 per multiply-add): the MLPs as ``bench.work.mlp_flops`` counts
them; each cross layer's two matmuls, ``x V`` and ``(x V) W``, are
``4 B F r`` forward and twice that backward (the input's gradient and
the weights'), F the width of x0 and r the low rank.  The elementwise
terms are left out.

HBM bytes:

- lookup: one read of each looked-up row (every id is live), one write
  of the pooled outputs (f32);
- row update: one read of the pooled outputs' gradients (f32), one read
  and one write of each distinct touched row and of its f32 accumulator;
- the step: both, plus the dense side's read of the pooled outputs and
  write of their gradients.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.work import mlp_flops

POOLED_BYTES = 4        # the lookup's pooled outputs and their gradients
ACC_BYTES = 4           # one f32 accumulator per row


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    cross_flops: float
    lookup_bytes: float
    update_bytes: float
    step_bytes: float


def cross_flops(batch: int, config: dict) -> float:
    width = (len(config["num_embeddings_per_feature"]) + 1) \
        * config["embedding_dim"]
    return (3 * 4.0 * batch * width * config["dcn_low_rank_dim"]
            * config["dcn_num_layers"])


def step_work(config: dict, batch: int, distinct_rows: float) -> Work:
    """``distinct_rows``: distinct arena rows among the batch's ids."""
    from bench.reference_dcnv2 import mlp_sizes
    d = config["embedding_dim"]
    item = {"bfloat16": 2, "float32": 4}[config["dtype"]]
    tables = len(config["num_embeddings_per_feature"])
    ids = float(batch) * sum(config["multi_hot_sizes"])
    pooled = float(batch) * tables * d * POOLED_BYTES
    lookup = ids * d * item + pooled
    update = pooled + float(distinct_rows) * 2 * (d * item + ACC_BYTES)
    cross = cross_flops(batch, config)
    return Work(flops=mlp_flops(batch, mlp_sizes(config)) + cross,
                cross_flops=cross, lookup_bytes=lookup, update_bytes=update,
                step_bytes=lookup + update + 2 * pooled)


def device_count_fn(base: np.ndarray, rows_max: int):
    """jitted ``gidx -> distinct rows`` of a (B, W) id array, on the
    device (sort, then count the changes)."""
    import jax
    import jax.numpy as jnp

    def count(gidx):
        rows = jnp.sort((gidx + jnp.asarray(base, jnp.int32)[None, :])
                        .reshape(-1))
        return 1 + jnp.sum(rows[1:] != rows[:-1])

    del rows_max
    return jax.jit(count)
