"""Public op: fused multi-table embedding bag with custom VJP.

``fused_embedding_lookup`` is the user-facing op: it packs a list of tables
into a zero-row arena (done once, at placement time, by
``repro.embedding``), pads the feature dim to 128 lanes, rebases per-table
indices, and dispatches the Pallas kernel (interpret mode on CPU, compiled
on TPU).  Backward is the row-wise scatter-add from ``ref.py`` (the
backward FBGEMM kernel would mirror the forward's scalar-prefetch pattern;
on the paper's cost model it is bwd_comp = bwd_scale x fwd traffic); this
is the op calibration prices.  The placed step's lookup
(``repro.embedding.sharded``) is not this op: its backward sorts the slots
by arena row and sums them with ``backward.py``'s kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.embedding_bag.kernel import embedding_bag_fused
from repro.kernels.embedding_bag.ref import (embedding_bag_grad_ref,
                                             embedding_bag_ref)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def pad_dim(d: int) -> int:
    return int(np.ceil(d / 128) * 128)


def build_arena(tables: list[jax.Array]):
    """Stack tables into a zero-row arena. Returns (arena, base_rows)."""
    dim = max(t.shape[1] for t in tables)
    dp = pad_dim(dim)
    parts = [jnp.zeros((1, dp), tables[0].dtype)]
    bases = []
    row = 1
    for t in tables:
        bases.append(row)
        pad = ((0, 0), (0, dp - t.shape[1]))
        parts.append(jnp.pad(t, pad))
        row += t.shape[0]
    return jnp.concatenate(parts, axis=0), np.asarray(bases)


def rebase_indices(indices: jax.Array, base_rows: np.ndarray) -> jax.Array:
    """indices: (T, B, P) per-table rows, -1 = padded slot -> arena rows."""
    base = jnp.asarray(base_rows)[:, None, None]
    return jnp.where(indices >= 0, indices + base, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def embedding_bag(arena, indices):
    """arena: (R, D128); indices: (N, P) arena rows -> pooled sums (N, D128)."""
    return embedding_bag_fused(arena, indices, interpret=_use_interpret())


def _fwd(arena, indices):
    return embedding_bag(arena, indices), (arena.shape, indices)


def _bwd(res, g):
    arena_shape, indices = res
    return embedding_bag_grad_ref(arena_shape, indices, g), None


embedding_bag.defvjp(_fwd, _bwd)


def fused_embedding_lookup(arena, base_rows, indices):
    """Multi-table fused lookup.

    indices: (T, B, P) per-table row ids (-1 padding).
    Returns (T, B, D128) pooled embeddings.
    """
    T, B, P = indices.shape
    flat = rebase_indices(indices, base_rows).reshape(T * B, P)
    out = embedding_bag(arena, flat)
    return out.reshape(T, B, -1)


def fused_embedding_lookup_ref(arena, base_rows, indices):
    T, B, P = indices.shape
    flat = rebase_indices(indices, base_rows).reshape(T * B, P)
    return embedding_bag_ref(arena, flat).reshape(T, B, -1)
