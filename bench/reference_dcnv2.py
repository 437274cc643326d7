"""Plain float32 reference of the placed DLRM-DCNv2 train step.

It imports nothing of the program.  From the run's seed it makes the same
initial weights (``bench.weights``, bf16 values widened to float32, and
the cross layers of ``cross_params``) and the same batches (the cell's
batch function), and runs DLRM-DCNv2's mathematics on them in float32 at
``HIGHEST`` matmul precision:

- each table's bag: the sum of the rows of its ids (a fixed number per
  sample, no padding);
- bottom MLP; x0 = concat(dense representation, the 26 pooled tables);
  three low-rank cross layers ``x_{l+1} = x0 * (x_l V_l W_l + b_l) + x_l``;
  top MLP; mean binary cross-entropy with logits;
- row-wise Adagrad on the arena rows (the accumulator adds the row mean
  of the squared gradient; rows no bag touched keep their values) and
  Adam on the dense parameters.

It never builds an f32 arena: the rows the checked steps touch are
gathered once, exactly, from the bf16 initial arena into an f32 copy (at
most 3 x 8,192 x 214 rows), and the steps run on that copy.  The MLPs
and the cross network run in blocks of ``BLOCK`` samples.

``quant="fp8"`` turns the reference into the control, computed a step
below the configuration's bf16, as ``bench.reference`` does: parameters
kept in float8 e4m3 (rounded after they are made and after every
update), and the values entering the lookups' sums and the matmuls
rounded the same way; gradients pass through unchanged.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from bench.reference import Readings, _adam, _same, _stored, fp8
from bench.traffic_gen import key_of
from bench.weights import arena_shard, dense_params

BLOCK = 1024
DENSE = ("bottom", "cross", "top")


@dataclasses.dataclass(frozen=True)
class Columns:
    """The column layout of one shard, as the benchmark derives it."""
    base: np.ndarray        # (W,) first arena row of each column's table
    table: np.ndarray       # (W,) table id of each column
    rows: np.ndarray        # (W,) rows of each column's table

    @property
    def n_tables(self) -> int:
        return int(self.table.max()) + 1


def cross_params(key, width: int, rank: int, n_layers: int, dtype) -> list:
    """Xavier-normal V (width, rank) and W (rank, width), zero bias."""
    import jax
    import jax.numpy as jnp
    std = np.sqrt(2.0 / (width + rank))
    out = []
    for i in range(n_layers):
        kv, kw = jax.random.split(jax.random.fold_in(key, i))
        out.append({
            "V": (jax.random.normal(kv, (width, rank), jnp.float32)
                  * std).astype(dtype),
            "W": (jax.random.normal(kw, (rank, width), jnp.float32)
                  * std).astype(dtype),
            "b": jnp.zeros((width,), dtype)})
    return out


def weight_keys(seed: int) -> dict:
    return {name: key_of(seed, name) for name in ("arenas", *DENSE)}


def dense_weights(keys: dict, config: dict, dtype) -> dict:
    """{"bottom", "cross", "top"} of the configuration, from the keys."""
    sizes = mlp_sizes(config)
    out = dense_params(keys, sizes, dtype)
    out["cross"] = cross_params(keys["cross"], sizes["top"][0],
                                config["dcn_low_rank_dim"],
                                config["dcn_num_layers"], dtype)
    return out


def mlp_sizes(config: dict) -> dict:
    """Bottom and top MLP widths, input first (the top's input is x0)."""
    d = config["embedding_dim"]
    n = len(config["num_embeddings_per_feature"])
    return {"bottom": [config["num_dense_features"],
                       *config["dense_arch_layer_sizes"]],
            "top": [(n + 1) * d, *config["over_arch_layer_sizes"]]}


def leaf_names(dense: dict) -> list[str]:
    """``cross.0.V``-style names of the dense leaves, in tree order."""
    return [f"{name}.{i}.{k}" for name in DENSE
            for i, layer in enumerate(dense[name]) for k in sorted(layer)]


def dense_leaves(dense: dict) -> list:
    return [layer[k] for name in DENSE
            for layer in dense[name] for k in sorted(layer)]


def _blocks(x):
    n = max(1, x.shape[0] // BLOCK)
    return x.reshape(n, x.shape[0] // n, *x.shape[1:])


def _pooled(rows, local, table, n_tables, q):
    """rows (U, D) f32; local (b, W) indices into rows -> (b, M, D)."""
    import jax
    got = q(rows)[local]                                     # (b, W, D)
    return jax.ops.segment_sum(got.transpose(1, 0, 2), table,
                               num_segments=n_tables).transpose(1, 0, 2)


def _loss_sum(dense, pooled, x, y, q):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def mlp(layers, h):
        for i, layer in enumerate(layers):
            h = jnp.dot(q(h), q(layer["w"]), precision=hi) + layer["b"]
            if i < len(layers) - 1:
                h = jax.nn.relu(h)
        return h

    d = mlp(dense["bottom"], x)
    x0 = q(jnp.concatenate([d[:, None, :], pooled], axis=1)
           .reshape(x.shape[0], -1))
    h = x0
    for layer in dense["cross"]:
        low = jnp.dot(q(h), q(layer["V"]), precision=hi)
        h = x0 * (jnp.dot(q(low), q(layer["W"]), precision=hi)
                  + layer["b"]) + h
    z = mlp(dense["top"], h)[:, 0]
    return jnp.sum(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def _step_grads(dense, rows, local, x, y, *, table, n_tables, q):
    """Mean loss, the dense gradients and the f32 gradient of ``rows``."""
    import jax
    import jax.numpy as jnp

    def loss(dense, rows, local, x, y):
        return _loss_sum(dense, _pooled(rows, local, table, n_tables, q), x,
                         y, q)

    grad = jax.value_and_grad(loss, argnums=(0, 1))

    def body(carry, blk):
        total, gd, gr = carry
        lb, (d, r) = grad(dense, rows, *blk)
        return (total + lb, jax.tree.map(jnp.add, gd, d), gr + r), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, dense),
            jnp.zeros_like(rows))
    (total, gd, gr), _ = jax.lax.scan(
        body, zero, (_blocks(local), _blocks(x), _blocks(y)))
    inv = 1.0 / x.shape[0]
    return total * inv, jax.tree.map(lambda g: g * inv, gd), gr * inv


def _row_adagrad(rows, acc, g, lr, eps):
    import jax.numpy as jnp
    acc = acc + jnp.mean(g * g, axis=-1)
    return rows - lr * g / (jnp.sqrt(acc) + eps)[:, None], acc


class Reference:
    """The reference (or, with ``quant="fp8"``, the control) of a
    DLRM-DCNv2 cell on one chip; compiled once, run for any seed."""

    def __init__(self, config: dict, cols: Columns, rows_max: int,
                 batch_fn, device, quant: str | None = None):
        import jax
        import jax.numpy as jnp
        self.config, self.cols, self.rows_max = config, cols, rows_max
        self.batch_fn, self.device = batch_fn, device
        q = {None: _same, "fp8": fp8}[quant]
        eo, do = config["emb_optimizer"], config["dense_optimizer"]
        self.dtype = jnp.dtype(config["dtype"])
        self._store = (_same if quant is None
                       else jax.jit(functools.partial(_stored, q=q)))
        self._grads = jax.jit(functools.partial(
            _step_grads, table=jnp.asarray(cols.table, jnp.int32),
            n_tables=cols.n_tables, q=q))
        self._rows = jax.jit(functools.partial(_row_adagrad, lr=eo["lr"],
                                               eps=eo["eps"]))
        self._adam = jax.jit(functools.partial(
            _adam, lr=do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]))
        one = jax.sharding.SingleDeviceSharding(device)
        self._touched = jax.jit(self._touched_rows, out_shardings=one)
        self._gather = jax.jit(self._initial_rows, out_shardings=one)

    def _touched_rows(self, gidxs):
        """Sorted arena rows the batches look up, padded with rows_max."""
        import jax.numpy as jnp
        base = jnp.asarray(self.cols.base, jnp.int32)
        rows = jnp.concatenate([(g + base[None, :]).reshape(-1)
                                for g in gidxs])
        return jnp.unique(rows, size=rows.shape[0], fill_value=self.rows_max)

    def _initial_rows(self, key, rows):
        """f32 copies of the bf16 initial arena's ``rows``."""
        import jax.numpy as jnp
        arena = arena_shard(key, 0, self.rows_max,
                            self.config["embedding_dim"], self.dtype)
        return arena[jnp.minimum(rows, self.rows_max - 1)].astype(
            jnp.float32)

    def run(self, seed: int, sizes, n_steps: int, sample: tuple) -> Readings:
        """``n_steps`` steps from the seed's weights on its batches 0..;
        ``sample`` = (shard ids, arena rows, ...) of the rows to read back
        after the first."""
        import jax
        import jax.numpy as jnp
        del sizes
        keys = weight_keys(seed)
        batches = [self.batch_fn(key_of(seed, "batch", t))
                   for t in range(n_steps)]
        touched = self._touched([b["gidx"] for b in batches])
        rows0 = self._store(self._gather(keys["arenas"], touched))
        rows, acc = rows0, jnp.zeros(rows0.shape[:1], jnp.float32)
        base = jnp.asarray(self.cols.base, jnp.int32)
        dense0 = jax.device_put(jax.tree.map(
            lambda a: a.astype(jnp.float32),
            dense_weights(keys, self.config, self.dtype)), self.device)
        dense = self._store(jax.tree.map(jnp.copy, dense0))
        m = jax.tree.map(jnp.zeros_like, dense)
        v = jax.tree.map(jnp.zeros_like, dense)
        at = jnp.searchsorted(touched, jnp.asarray(sample[1], jnp.int32))
        losses, grad_norms = [], {}
        for t, batch in enumerate(batches):
            local = jnp.searchsorted(touched, batch["gidx"] + base[None, :])
            loss, gd, gr = self._grads(dense, rows, local, batch["dense"],
                                       batch["labels"])
            rows, acc = self._rows(rows, acc, gr)
            rows = self._store(rows)
            if t == 0:
                grad_norms = {"arenas": float(jnp.linalg.norm(gr))}
                grad_norms.update(zip(leaf_names(gd), (
                    float(jnp.linalg.norm(g)) for g in dense_leaves(gd))))
                got_rows = np.asarray(rows[at])
                got_acc = np.asarray(acc[at])
            dense, m, v = self._adam(dense, m, v, gd, float(t + 1))
            dense = self._store(dense)
            losses.append(float(loss))
        change = {"arenas": float(jnp.linalg.norm(rows - rows0))}
        change.update(zip(leaf_names(dense), (
            float(jnp.linalg.norm(a - b)) for a, b in
            zip(dense_leaves(dense), dense_leaves(dense0)))))
        return Readings(losses, grad_norms, change, got_rows, got_acc)
