"""Weights of a step cell, made on the device from the run's seed.

The benchmark makes the initial parameters itself, in the type the
configuration serves them in, so that the program under test and the
plain reference start from the same numbers without the reference taking
anything the program made.  Shard ``s`` of the arenas depends only on
``(seed, s)``, so the reference can make one shard at a time.  The
jitted makers take keys, never the seed, so every seed runs the same
compiled programs.

Parameters follow the layout the program's ``DLRM`` takes:
``{"arenas": (n_shards, rows_max, dim), "bottom": [{"w", "b"}, ...],
"top": [...]}``.  Arenas are N(0, 0.01**2); each MLP layer is He-normal
with zero bias.
"""

from __future__ import annotations

import numpy as np

from bench.traffic_gen import key_of

ARENA_SCALE = 0.01
MLPS = ("bottom", "top")


def weight_keys(seed: int) -> dict:
    return {name: key_of(seed, name) for name in ("arenas", *MLPS)}


def mlp_sizes(config: dict) -> dict:
    """Layer widths of the bottom and top MLPs, input first."""
    n, d = config["n_tables"], config["embed_dim"]
    inter = (n + 1) * n // 2 + d
    return {"bottom": [config["n_dense_features"], *config["bottom_mlp"], d],
            "top": [inter, *config["top_mlp"], 1]}


def arena_shard(key, s, rows: int, dim: int, dtype):
    """Shard ``s`` (an int or a traced index) of the arenas."""
    import jax
    k = jax.random.fold_in(key, s)
    return (jax.random.normal(k, (rows, dim), dtype) * ARENA_SCALE
            ).astype(dtype)


def make_arenas(key, n_shards: int, rows: int, dim: int, dtype,
                mesh=None, axis: str = "model"):
    """All shards stacked; with ``mesh``, each device makes its own."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    if mesh is None:
        return jnp.stack([arena_shard(key, s, rows, dim, dtype)
                          for s in range(n_shards)])

    def local(key, shard_id):
        return arena_shard(key, shard_id[0], rows, dim, dtype)[None]

    return jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axis)),
                         out_specs=P(axis, None, None), check_vma=False)(
        key, jnp.arange(n_shards, dtype=jnp.uint32))


def dense_params(keys: dict, sizes: dict, dtype) -> dict:
    import jax
    import jax.numpy as jnp
    return {name: [
        {"w": (jax.random.normal(jax.random.fold_in(keys[name], i),
                                 (n_in, n_out), jnp.float32)
               * np.sqrt(2.0 / n_in)).astype(dtype),
         "b": jnp.zeros((n_out,), dtype)}
        for i, (n_in, n_out) in enumerate(zip(sizes[name][:-1],
                                              sizes[name][1:]))]
        for name in MLPS}


def leaf_names(dense: dict) -> list[str]:
    """``bottom.0.w``-style names of the dense leaves, in tree order."""
    return [f"{name}.{i}.{k}" for name in MLPS
            for i, layer in enumerate(dense[name]) for k in sorted(layer)]


def dense_leaves(dense: dict) -> list:
    return [layer[k] for name in MLPS
            for layer in dense[name] for k in sorted(layer)]
