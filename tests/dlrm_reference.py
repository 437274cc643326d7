"""Plain float32 reference of the placed DLRM step, for the tests.

Straightforward ``jax.numpy`` at ``HIGHEST`` matmul precision, with no
kernel, no plan-grouped indices and no custom gradient: each table's bags
are gathered from its own rows and summed, the dense side follows the
configuration's interaction (the pairwise dot, or DCN-v2's low-rank cross
network, arXiv:2008.13535), the loss is the mean binary cross-entropy,
and the gradients are autodiff's.  ``adagrad_step`` is one row-wise
Adagrad step on every arena row from the dense f32 gradient (a row no
bag touched has a zero gradient and keeps its values).

Inputs: ``arenas`` (S, R, D) and the plan only to find table t's rows
(shard, base row, row count); ``ids`` (B, M, P) per-table row ids with -1
padding, tables in id order.
"""

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def table_arenas(arenas, plan):
    """Table t's rows, f32: a list of (rows_t, D) views."""
    out = []
    for t in range(plan.n_tables):
        s, k = map(int, np.argwhere(plan.slot_table == t)[0])
        base, rows = int(plan.base_rows[s, k]), int(plan.table_rows[t])
        out.append(arenas[s, base:base + rows].astype(jnp.float32))
    return out


def pooled(tables, ids):
    """(B, M, D): each table's bag summed over its live ids."""
    out = []
    for t, rows in enumerate(tables):
        i = ids[:, t]
        got = jnp.take(rows, jnp.maximum(i, 0), axis=0)
        out.append(jnp.sum(jnp.where((i >= 0)[..., None], got, 0.0), 1))
    return jnp.stack(out, axis=1)


def mlp(layers, x):
    for i, layer in enumerate(layers):
        x = jnp.dot(x, layer["w"].astype(jnp.float32), precision=HI) \
            + layer["b"].astype(jnp.float32)
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def logits(dense_params, pooled_tables, dense, interaction):
    d = mlp(dense_params["bottom"], dense)
    feats = jnp.concatenate([d[:, None, :], pooled_tables], axis=1)
    if interaction == "dcn":
        x0 = feats.reshape(feats.shape[0], -1)
        x = x0
        for layer in dense_params["cross"]:
            v, w, b = (layer[k].astype(jnp.float32) for k in "VWb")
            x = x0 * (jnp.dot(jnp.dot(x, v, precision=HI), w, precision=HI)
                      + b) + x
    else:
        z = jnp.einsum("bid,bjd->bij", feats, feats, precision=HI)
        iu, ju = np.triu_indices(feats.shape[1], k=1)
        x = jnp.concatenate([d, z[:, iu, ju]], axis=-1)
    return mlp(dense_params["top"], x)[:, 0]


def bce(z, y):
    return jnp.mean(jnp.maximum(z, 0) - z * y
                    + jnp.log1p(jnp.exp(-jnp.abs(z))))


def loss_and_grads(params, plan, ids, dense, labels, interaction):
    """(loss, gradient of every dense leaf, gradient of the arenas), f32."""
    dense_params = {k: v for k, v in params.items() if k != "arenas"}
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), dense_params)

    def loss(dp, arenas):
        return bce(logits(dp, pooled(table_arenas(arenas, plan), ids),
                          dense, interaction), labels)

    value, (gd, ga) = jax.value_and_grad(loss, argnums=(0, 1))(
        f32, params["arenas"].astype(jnp.float32))
    return value, gd, ga


def adagrad_step(arenas, acc, grad, lr, eps):
    """Row-wise Adagrad on every row of (S, R, D) f32 arenas."""
    acc = acc + jnp.mean(grad * grad, axis=-1)
    return arenas - lr * grad / (jnp.sqrt(acc) + eps)[..., None], acc
