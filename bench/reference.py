"""Plain float32 reference of the placed DLRM train step.

It imports nothing of the program.  From the run's seed it makes the same
initial weights (``bench.weights``, bf16 values widened to float32) and
the same batches (``bench.traffic_gen``), and runs the configuration's
mathematics on them in float32 at ``HIGHEST`` matmul precision:

- each shard's lookups: gather the rows of its slots and sum each bag's
  live rows (``-1`` slots add nothing);
- bottom MLP, pairwise dot interaction with the dense representation
  first, top MLP, mean binary cross-entropy with logits;
- row-wise Adagrad on each shard (the accumulator adds the row mean of
  the squared gradient; rows no bag touched keep their values) and Adam
  on the MLPs.

Every shard's arena, accumulator and gradient live on the device that
holds that shard in the timed path (all on one chip for one-chip cells),
and the lookups, the MLPs and the gradient scatter run in blocks of
``BLOCK`` batch rows, so that the reference fits beside nothing else.

``quant="fp8"`` turns the reference into the control, computed a step
below the configuration's bf16: the parameters are kept in float8 e4m3
(rounded, with a per-tensor scale, after they are made and after every
update), and the values that enter the lookups' sums, the matmuls and
the interaction are rounded the same way; gradients pass through the
rounding unchanged, and the optimizers' state stays float32.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from bench.layout import Layout
from bench.traffic_gen import key_of
from bench.weights import (arena_shard, dense_leaves, dense_params, leaf_names,
                           weight_keys)

BLOCK = 4096


@dataclasses.dataclass
class Readings:
    """What a run of three steps is judged by (program or reference)."""
    losses: list            # loss of each step
    grad_norms: dict        # leaf -> norm of the first step's gradient
    change_norms: dict      # leaf -> norm of the change after the steps
    rows: np.ndarray        # (N, D) sampled arena rows after step 1
    acc: np.ndarray         # (N,) their accumulators after step 1


def fp8(x):
    """x rounded to float8 e4m3 (3 mantissa bits, normal exponents from
    -6, largest value 448) with a per-tensor scale, in float32
    arithmetic so that no backend can fold the rounding away; the
    gradient passes through unchanged."""
    import jax
    import jax.numpy as jnp
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                                  / 448.0)
    v = jax.lax.stop_gradient(x) / scale
    a = jnp.abs(v)
    step = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -6))) - 3)
    y = jnp.sign(v) * jnp.minimum(jnp.round(a / step) * step, 448.0) * scale
    return x + jax.lax.stop_gradient(y - x)


def _stored(tree, q):
    """The parameters as kept between steps: each leaf through ``q``."""
    import jax
    return jax.tree.map(q, tree)


def _same(x):
    return x


def _blocks(x):
    """(B, ...) -> (B / block, block, ...), blocks of ``BLOCK`` rows or
    of the whole batch where it is smaller."""
    n = max(1, x.shape[0] // BLOCK)
    return x.reshape(n, x.shape[0] // n, *x.shape[1:])


def _lookup(arena, bases, idx, q):
    """arena (R, D) f32; idx (B, K, P) -> (B, K, D) pooled sums."""
    import jax
    import jax.numpy as jnp
    hp = q(arena)

    def one(blk):
        live = blk >= 0
        rows = jnp.take(hp, jnp.where(live, blk + bases[None, :, None], 0),
                        axis=0)
        return jnp.sum(jnp.where(live[..., None], rows, 0.0), axis=2)

    out = jax.lax.map(one, _blocks(idx))
    return out.reshape(idx.shape[0], idx.shape[1], arena.shape[1])


def _mlp(layers, x, q):
    import jax
    import jax.numpy as jnp
    for i, layer in enumerate(layers):
        x = jnp.dot(q(x), q(layer["w"]),
                    precision=jax.lax.Precision.HIGHEST) + layer["b"]
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def _loss_sum(dense, pooled, x, y, q):
    import jax
    import jax.numpy as jnp
    d = _mlp(dense["bottom"], x, q)
    feats = q(jnp.concatenate([d[:, None, :], pooled], axis=1))
    z = jnp.einsum("bid,bjd->bij", feats, feats,
                   precision=jax.lax.Precision.HIGHEST)
    iu, ju = np.triu_indices(feats.shape[1], k=1)
    logits = _mlp(dense["top"], jnp.concatenate([d, z[:, iu, ju]], -1),
                  q)[:, 0]
    return jnp.sum(jnp.maximum(logits, 0) - logits * y
                   + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def _dense_grads(dense, pooled, x, y, q):
    """Mean loss, its gradient for the MLPs and for the pooled lookups."""
    import jax
    import jax.numpy as jnp
    grad = jax.value_and_grad(functools.partial(_loss_sum, q=q),
                              argnums=(0, 1))

    def body(carry, blk):
        loss, g = carry
        lb, (gd, gp) = grad(dense, *blk)
        return (loss + lb, jax.tree.map(jnp.add, g, gd)), gp

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, dense))
    (loss, gd), gp = jax.lax.scan(
        body, zero, (_blocks(pooled), _blocks(x), _blocks(y)))
    inv = 1.0 / x.shape[0]
    return (loss * inv, jax.tree.map(lambda g: g * inv, gd),
            gp.reshape(pooled.shape) * inv)


def _shard_update(arena, acc, bases, idx, g, lr, eps):
    """Row-wise Adagrad on one shard from the pooled gradients g (B, K, D);
    returns the new arena, accumulator and the gradient's squared norm."""
    import jax
    import jax.numpy as jnp
    def body(grad, blk):
        ib, gb = blk
        live = ib >= 0
        rows = jnp.where(live, ib + bases[None, :, None], 0)
        contrib = jnp.where(live[..., None], gb[:, :, None, :], 0.0)
        return grad.at[rows.reshape(-1)].add(
            contrib.reshape(-1, arena.shape[1])), None

    grad, _ = jax.lax.scan(body, jnp.zeros_like(arena),
                           (_blocks(idx), _blocks(g)))
    acc = acc + jnp.mean(grad * grad, axis=-1)
    arena = arena - lr * grad / (jnp.sqrt(acc) + eps)[:, None]
    return arena, acc, jnp.sum(grad * grad)


def _adam(p, m, v, g, t, lr, b1, b2, eps):
    import jax
    import jax.numpy as jnp
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    p = jax.tree.map(lambda p_, m_, v_: p_ - lr * (m_ / bc1)
                     / (jnp.sqrt(v_ / bc2) + eps), p, m, v)
    return p, m, v


class Reference:
    """The reference (or, with ``quant="fp8"``, the control) of one cell;
    compiled once, run for any seed."""

    def __init__(self, config: dict, layout: Layout, batch_fn, devices,
                 quant: str | None = None):
        import jax
        import jax.numpy as jnp
        self.config, self.layout, self.batch_fn = config, layout, batch_fn
        self.devices = list(devices)
        q = {None: _same, "fp8": fp8}[quant]
        eo, do = config["emb_optimizer"], config["dense_optimizer"]
        self.dtype = jnp.dtype(config["dtype"])
        self._store = (_same if quant is None
                       else jax.jit(functools.partial(_stored, q=q),
                                    donate_argnums=0))
        self._lookup = jax.jit(functools.partial(_lookup, q=q))
        self._dense = jax.jit(functools.partial(_dense_grads, q=q))
        self._update = jax.jit(functools.partial(
            _shard_update, lr=eo["lr"], eps=eo["eps"]), donate_argnums=(0, 1))
        self._adam = jax.jit(functools.partial(
            _adam, lr=do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]))
        self._arena = {
            d: jax.jit(functools.partial(
                self._arena0, rows=layout.rows_max, dim=config["embed_dim"]),
                static_argnums=1,
                out_shardings=jax.sharding.SingleDeviceSharding(d))
            for d in self.devices}

    def _arena0(self, key, s, rows, dim):
        import jax.numpy as jnp
        return arena_shard(key, s, rows, dim, self.dtype).astype(jnp.float32)

    def device(self, s: int):
        return self.devices[s % len(self.devices)]

    def _read(self, arenas, accs, sample):
        """The sampled rows and their accumulators, from each shard."""
        shard_ids, rows = sample[:2]
        got_rows = np.zeros((rows.shape[0], self.config["embed_dim"]),
                            np.float32)
        got_acc = np.zeros((rows.shape[0],), np.float32)
        for s in range(self.layout.n_shards):
            at = np.flatnonzero(shard_ids == s)
            if at.size:
                got_rows[at] = np.asarray(arenas[s][rows[at]])
                got_acc[at] = np.asarray(accs[s][rows[at]])
        return got_rows, got_acc

    def run(self, seed: int, sizes: dict, n_steps: int,
            sample: tuple) -> Readings:
        """``n_steps`` steps from the seed's weights on its batches 0..;
        ``sample`` = (shard ids, arena rows, ...) of the rows to read
        back."""
        import jax
        import jax.numpy as jnp
        L, dev0 = self.layout, self.devices[0]
        K = L.k_max
        keys = weight_keys(seed)
        arenas = [self._store(self._arena[self.device(s)](keys["arenas"], s))
                  for s in range(L.n_shards)]
        accs = [jax.device_put(jnp.zeros((L.rows_max,), jnp.float32),
                               self.device(s)) for s in range(L.n_shards)]
        bases = [jax.device_put(jnp.asarray(L.base_rows[s], jnp.int32),
                                self.device(s)) for s in range(L.n_shards)]
        dense0 = jax.device_put(jax.tree.map(
            lambda a: a.astype(jnp.float32),
            dense_params(keys, sizes, self.dtype)), dev0)
        dense = self._store(jax.tree.map(jnp.copy, dense0))
        m = jax.tree.map(jnp.zeros_like, dense)
        v = jax.tree.map(jnp.zeros_like, dense)
        order = L.table_order()
        losses, grad_norms = [], {}
        for t in range(n_steps):
            batch = self.batch_fn(key_of(seed, "batch", t))
            idx = [jax.device_put(batch["gidx"][:, s * K:(s + 1) * K],
                                  self.device(s)) for s in range(L.n_shards)]
            pooled = jnp.concatenate(
                [jax.device_put(self._lookup(arenas[s], bases[s], idx[s]),
                                dev0) for s in range(L.n_shards)], axis=1)
            loss, gd, gp = self._dense(dense, pooled[:, order],
                                       batch["dense"], batch["labels"])
            del batch, pooled
            gslots = jnp.zeros((gp.shape[0], L.n_slots, gp.shape[2]),
                               jnp.float32).at[:, order].set(gp)
            sq = 0.0
            for s in range(L.n_shards):
                g_s = jax.device_put(gslots[:, s * K:(s + 1) * K],
                                     self.device(s))
                arenas[s], accs[s], sq_s = self._update(
                    arenas[s], accs[s], bases[s], idx[s], g_s)
                arenas[s] = self._store(arenas[s])
                sq = sq + float(sq_s)
            del gslots, idx
            if t == 0:
                grad_norms = {"arenas": float(np.sqrt(sq))}
                grad_norms.update(zip(leaf_names(gd), (
                    float(jnp.linalg.norm(g)) for g in dense_leaves(gd))))
                got_rows, got_acc = self._read(arenas, accs, sample)
            dense, m, v = self._adam(dense, m, v, gd, float(t + 1))
            dense = self._store(dense)
            losses.append(float(loss))
        sq = 0.0
        for s in range(L.n_shards):
            d = arenas[s] - self._arena[self.device(s)](keys["arenas"], s)
            sq += float(jnp.sum(d * d))
        change = {"arenas": float(np.sqrt(sq))}
        change.update(zip(leaf_names(dense), (
            float(jnp.linalg.norm(a - b)) for a, b in
            zip(dense_leaves(dense), dense_leaves(dense0)))))
        return Readings(losses, grad_norms, change, got_rows, got_acc)
