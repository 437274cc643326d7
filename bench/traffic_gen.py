"""The one generator of step traffic: CTR batches with Zipf-drawn indices.

A traffic file (``bench/traffic/<name>.json``) gives the parameters;
the configuration gives the tables.  With ``"index_law": "zipf"`` the
indices of a table's bags follow a Zipf law with that table's exponent
from the pool, truncated to its rows; with ``"uniform"`` they are uniform
over its rows (a Zipf law of exponent 0).  A fixed pseudo-random
permutation of each table's rows, salted with the table id, turns ranks
into rows, so hot rows are not contiguous.  Each table
fills its rounded pooling factor, capped at the configuration's
``max_pooling``, with indices and the rest of its slots with ``-1``.
Dense features are standard normal and labels Bernoulli(``label_rate``).

Batch ``i`` of a run comes from ``key_of(seed, "batch", i)``, so the
same seed gives the same batches, and every seed gives batches of the
same shapes, bag lengths and laws.
"""

from __future__ import annotations

import zlib

import numpy as np

from bench.layout import Layout
from bench.pool import POOLING


def key_of(seed: int, *stream):
    """A PRNG key for ``stream`` of run ``seed``; any seed below 2**62."""
    import jax
    key = jax.random.PRNGKey(0)
    for word in (seed & 0x7FFFFFFF, seed >> 31, *stream):
        if isinstance(word, str):
            word = zlib.crc32(word.encode()) & 0x7FFFFFFF
        key = jax.random.fold_in(key, word)
    return key


INDEX_LAWS = ("zipf", "uniform")


def slot_params(layout: Layout, raw: np.ndarray, zipf_s: np.ndarray,
                max_pooling: int, index_law: str) -> dict:
    """Per grouped slot: rows, bag length, Zipf exponent, hash salt."""
    if index_law not in INDEX_LAWS:
        raise ValueError(f"index_law {index_law!r} not in {INDEX_LAWS}")
    if index_law == "uniform":
        zipf_s = np.zeros_like(zipf_s)
    flat = layout.slot_table.reshape(-1)
    live = flat >= 0
    owner = np.maximum(flat, 0)
    pools = np.clip(np.rint(raw[owner, POOLING]), 1, max_pooling)
    return {"rows": np.where(live, layout.table_rows[owner], 1)
            .astype(np.int32),
            "pool": np.where(live, pools, 0).astype(np.int32),
            "s": np.where(live, zipf_s[owner], 1.0).astype(np.float32),
            "salt": owner.astype(np.uint32)}


def _bits(rows: np.ndarray) -> np.ndarray:
    """Bits of the smallest power of two at or above each row count."""
    return np.array([int(r - 1).bit_length() for r in rows], np.uint32)


def permute(rank, rows, bits, salt):
    """A bijection of [0, rows) per table: odd multipliers and xor-shifts
    mod 2**bits (each a bijection there), walked until the value falls
    below ``rows``."""
    import jax
    import jax.numpy as jnp
    mask = (jnp.uint32(1) << bits) - jnp.uint32(1)
    half = (bits + jnp.uint32(1)) >> jnp.uint32(1)
    add = (salt * jnp.uint32(0x9E3779B9)) & mask
    n = rows.astype(jnp.uint32)

    def mix(x):
        x = (x * jnp.uint32(0x9E3779B1)) & mask
        x = x ^ (x >> half)
        x = (x * jnp.uint32(0x85EBCA6B)) & mask
        x = x ^ (x >> half)
        return (x + add) & mask

    y = jax.lax.while_loop(lambda y: jnp.any(y >= n),
                           lambda y: jnp.where(y >= n, mix(y), y),
                           mix(rank.astype(jnp.uint32)))
    return y.astype(jnp.int32)


def zipf_rows(u, rows, s, bits, salt):
    """Rows drawn from a Zipf(s) law over ``rows`` ranks, by the inverse
    of the continuous law on [1, rows + 1), permuted to rows."""
    import jax.numpy as jnp
    a = 1.0 - s
    log_n = jnp.log(rows.astype(jnp.float32) + 1.0)
    safe = jnp.where(jnp.abs(a) < 1e-6, 1.0, a)
    x = jnp.where(jnp.abs(a) < 1e-6, u * log_n,
                  jnp.log1p(u * jnp.expm1(safe * log_n)) / safe)
    rank = jnp.clip(jnp.floor(jnp.exp(x)).astype(jnp.int32) - 1, 0, rows - 1)
    return permute(rank, rows, bits, salt)


def make_batch_fn(params: dict, batch: int, n_dense: int, max_pooling: int,
                  label_rate: float, out_shardings=None):
    """jitted ``key -> {"dense", "gidx", "labels"}`` in the plan layout."""
    import jax
    import jax.numpy as jnp
    rows = jnp.asarray(params["rows"])[None, :, None]
    pool = jnp.asarray(params["pool"])[None, :, None]
    s = jnp.asarray(params["s"])[None, :, None]
    salt = jnp.asarray(params["salt"])[None, :, None]
    bits = jnp.asarray(_bits(params["rows"]))[None, :, None]
    n_slots = params["rows"].shape[0]

    def build(key):
        k1, k2, k3 = jax.random.split(key, 3)
        u = jax.random.uniform(k1, (batch, n_slots, max_pooling))
        idx = zipf_rows(u, rows, s, bits, salt)
        slot = jnp.arange(max_pooling)[None, None, :]
        return {"dense": jax.random.normal(k2, (batch, n_dense), jnp.float32),
                "gidx": jnp.where(slot < pool, idx, -1),
                "labels": jax.random.bernoulli(k3, label_rate, (batch,))
                .astype(jnp.float32)}

    return jax.jit(build, out_shardings=out_shardings)
