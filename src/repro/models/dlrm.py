"""DLRM recommender model (paper App. A.1, after Naumov et al. 2019).

Dense features -> bottom MLP; sparse features -> distributed embedding
lookups (table-wise model parallel, DreamShard-placed) -> pairwise dot
interaction with the dense representation -> top MLP -> CTR logit.

The dense parts are data-parallel (replicated params, batch-sharded
activations); the embedding arenas are model-parallel via
``repro.embedding.sharded``.

Each layer of the step runs under a named scope (``SCOPES``), so a
device profile of the step names its ops by layer: the forward op under
``jvp(<scope>)``, its backward under ``transpose(jvp(<scope>))``.  The
scopes are metadata only; the compiled step is the same without them.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.embedding import sharded as E
from repro.embedding.plan import PlacementPlan
from repro.optim import apply_updates

DENSE_PARAMS = ("bottom", "top")

BOTTOM_SCOPE = "dlrm.bottom"              # bottom MLP
EMBED_SCOPE = "dlrm.embed"                # the lookup and the slot reorder
INTERACT_SCOPE = "dlrm.interact"          # pairwise dot interaction
TOP_SCOPE = "dlrm.top"                    # top MLP
LOSS_SCOPE = "dlrm.loss"                  # BCE
EMB_UPDATE_SCOPE = "dlrm.emb_update"      # row-wise update + arena apply
DENSE_UPDATE_SCOPE = "dlrm.dense_update"  # dense optimizer + apply
SCOPES = (BOTTOM_SCOPE, EMBED_SCOPE, INTERACT_SCOPE, TOP_SCOPE, LOSS_SCOPE,
          EMB_UPDATE_SCOPE, DENSE_UPDATE_SCOPE)


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    n_dense_features: int = 13
    embed_dim: int = 128            # padded feature dim (plan.dim)
    bottom_mlp: tuple = (512, 256)
    top_mlp: tuple = (1024, 512, 256)
    n_tables: int = 50


def _mlp_init(key, sizes, dtype):
    params = []
    for n_in, n_out in zip(sizes[:-1], sizes[1:]):
        key, k = jax.random.split(key)
        params.append({
            "w": (jax.random.normal(k, (n_in, n_out))
                  * np.sqrt(2.0 / n_in)).astype(dtype),
            "b": jnp.zeros((n_out,), dtype)})
    return params


def _mlp(params, x):
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


class DLRM:
    def __init__(self, cfg: DLRMConfig, plan: PlacementPlan,
                 dtype=jnp.float32):
        self.cfg = cfg
        self.plan = plan
        self.dtype = dtype
        self.n_slots = plan.n_shards * plan.k_max

    def init_params(self, key):
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        n_inter = cfg.n_tables + 1          # tables + dense rep
        inter_dim = n_inter * (n_inter - 1) // 2 + cfg.embed_dim
        return {
            "arenas": E.init_arenas(k1, self.plan, self.dtype),
            "bottom": _mlp_init(k2, (cfg.n_dense_features, *cfg.bottom_mlp,
                                     cfg.embed_dim), self.dtype),
            "top": _mlp_init(k3, (inter_dim, *cfg.top_mlp, 1), self.dtype),
        }

    def _interact(self, dense_rep, sparse):
        """Pairwise dot interaction. sparse: (B, T, D); dense: (B, D)."""
        feats = jnp.concatenate([dense_rep[:, None, :], sparse], axis=1)
        z = jnp.einsum("bid,bjd->bij", feats, feats)
        n = feats.shape[1]
        iu, ju = np.triu_indices(n, k=1)
        return jnp.concatenate([dense_rep, z[:, iu, ju]], axis=-1)

    def forward(self, params, dense, grouped_indices, lookup_fn):
        """dense: (B, n_dense); grouped_indices: (B, S*K, P) (plan layout).

        lookup_fn: the sharded (or oracle) embedding lookup.
        Returns CTR logits (B,).
        """
        plan = self.plan
        with jax.named_scope(EMBED_SCOPE):
            bases = jnp.asarray(plan.base_rows)
            sparse_all = lookup_fn(params["arenas"], bases, grouped_indices)
            # drop padded slots, keep true tables in original order
            order = plan.grouped_index_order()
            keep = np.flatnonzero(order >= 0)
            inv = keep[np.argsort(order[keep], kind="stable")]
            sparse = jnp.take(sparse_all, jnp.asarray(inv), axis=1)
        with jax.named_scope(BOTTOM_SCOPE):
            dense_rep = _mlp(params["bottom"], dense.astype(self.dtype))
        with jax.named_scope(INTERACT_SCOPE):
            x = self._interact(dense_rep, sparse.astype(self.dtype))
        with jax.named_scope(TOP_SCOPE):
            return _mlp(params["top"], x)[:, 0]

    @staticmethod
    def loss(logits, labels):
        """Binary cross-entropy with logits."""
        with jax.named_scope(LOSS_SCOPE):
            logits = logits.astype(jnp.float32)
            return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                            + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def make_train_step(model: DLRM, lookup_fn, emb_opt, dense_opt):
    """One DLRM training step: ``emb_opt`` (row-wise) on the arenas and
    ``dense_opt`` on the MLPs.

    Returns ``step(params, emb_state, dense_state, batch) -> (params,
    emb_state, dense_state, loss)``; ``batch`` holds ``"dense"`` (B, n_dense),
    ``"gidx"`` (B, S*K, P) plan-grouped indices and ``"labels"`` (B,).
    """
    def step(params, emb_state, dense_state, batch):
        def loss_fn(p):
            logits = model.forward(p, batch["dense"], batch["gidx"],
                                   lookup_fn)
            return DLRM.loss(logits, batch["labels"])
        loss, g = jax.value_and_grad(loss_fn)(params)
        with jax.named_scope(EMB_UPDATE_SCOPE):
            eu, emb_state = emb_opt.update({"arenas": g["arenas"]},
                                           emb_state)
            arenas = apply_updates({"arenas": params["arenas"]}, eu)
        with jax.named_scope(DENSE_UPDATE_SCOPE):
            du, dense_state = dense_opt.update(
                {k: g[k] for k in DENSE_PARAMS}, dense_state)
            dense = apply_updates({k: params[k] for k in DENSE_PARAMS}, du)
        return {**dense, **arenas}, emb_state, dense_state, loss

    return step
