"""Minimal optax-style optimizers as pure-JAX pytree transforms.

Each optimizer is a pair of pure functions ``init(params) -> state`` and
``update(grads, state, params) -> (updates, state)``; ``apply_updates`` adds
updates to params.  Learning-rate may be a float or a callable step->lr
(used for the paper's linear decay schedule).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Union

import jax
import jax.numpy as jnp

Schedule = Union[float, Callable[[jnp.ndarray], jnp.ndarray]]


class OptState(NamedTuple):
    step: jnp.ndarray
    inner: Any


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], OptState]
    update: Callable[[Any, OptState, Any], tuple[Any, OptState]]


def _lr_at(lr: Schedule, step: jnp.ndarray) -> jnp.ndarray:
    return lr(step) if callable(lr) else jnp.asarray(lr)


def linear_decay(base_lr: float, total_steps: int) -> Callable:
    def sched(step):
        frac = jnp.clip(1.0 - step / max(total_steps, 1), 0.0, 1.0)
        return base_lr * frac
    return sched


def sgd(lr: Schedule, momentum: float = 0.0) -> Optimizer:
    def init(params):
        mu = jax.tree.map(jnp.zeros_like, params) if momentum else None
        return OptState(jnp.zeros((), jnp.int32), mu)

    def update(grads, state, params=None):
        step = state.step + 1
        lrv = _lr_at(lr, step)
        if momentum:
            mu = jax.tree.map(lambda m, g: momentum * m + g, state.inner, grads)
            upd = jax.tree.map(lambda m: -lrv * m, mu)
            return upd, OptState(step, mu)
        upd = jax.tree.map(lambda g: -lrv * g, grads)
        return upd, OptState(step, None)

    return Optimizer(init, update)


def adam(lr: Schedule, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8, weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        def zeros():
            return jax.tree.map(jnp.zeros_like, params)
        return OptState(jnp.zeros((), jnp.int32), (zeros(), zeros()))

    def update(grads, state, params=None):
        step = state.step + 1
        m, v = state.inner
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
        lrv = _lr_at(lr, step)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def one(m_, v_, p):
            upd = -lrv * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            if weight_decay and p is not None:
                upd = upd - lrv * weight_decay * p
            return upd

        if weight_decay:
            upd = jax.tree.map(one, m, v, params)
        else:
            upd = jax.tree.map(lambda m_, v_: one(m_, v_, None), m, v)
        return upd, OptState(step, (m, v))

    return Optimizer(init, update)


def adamw(lr: Schedule, b1: float = 0.9, b2: float = 0.999,
          eps: float = 1e-8, weight_decay: float = 0.01) -> Optimizer:
    return adam(lr, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)


def rowwise_adagrad(lr: Schedule, eps: float = 1e-8) -> Optimizer:
    """Row-wise Adagrad for embedding tables: one accumulator per row.

    Accumulates the row-mean squared gradient -- the standard optimizer for
    large embedding tables (one float of state per row instead of per elem).
    Falls back to full Adagrad for rank<2 leaves.
    """

    def init(params):
        def acc(p):
            if p.ndim >= 2:
                return jnp.zeros(p.shape[:-1], p.dtype)
            return jnp.zeros_like(p)
        return OptState(jnp.zeros((), jnp.int32), jax.tree.map(acc, params))

    def update(grads, state, params=None):
        step = state.step + 1
        lrv = _lr_at(lr, step)

        def one(a, g):
            if g.ndim >= 2:
                a = a + jnp.mean(g * g, axis=-1)
                scale = 1.0 / (jnp.sqrt(a) + eps)
                return a, -lrv * g * scale[..., None]
            a = a + g * g
            return a, -lrv * g / (jnp.sqrt(a) + eps)

        flat_a, treedef = jax.tree.flatten(state.inner)
        flat_g = treedef.flatten_up_to(grads)
        pairs = [one(a, g) for a, g in zip(flat_a, flat_g)]
        new_acc = treedef.unflatten([p[0] for p in pairs])
        upd = treedef.unflatten([p[1] for p in pairs])
        return upd, OptState(step, new_acc)

    return Optimizer(init, update)


@dataclasses.dataclass(frozen=True)
class RowWiseAdagrad:
    """Row-wise Adagrad that touches only the rows a step looked up.

    Not an ``Optimizer``: ``repro.models.dlrm.make_train_step`` recognises
    it and, in place of a gradient of the arenas, hands the pooled
    lookups' gradients to ``repro.embedding.sharded.rowwise_adagrad_rows``,
    which updates the looked-up rows in place and keeps no dense
    gradient.  Its state is one f32 accumulator per arena row."""
    lr: float
    eps: float = 1e-8

    def init(self, params):
        return OptState(jnp.zeros((), jnp.int32), jax.tree.map(
            lambda p: jnp.zeros(p.shape[:-1], jnp.float32), params))


def apply_updates(params, updates):
    return jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)
