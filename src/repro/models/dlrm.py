"""DLRM recommender model (paper App. A.1, after Naumov et al. 2019).

Dense features -> bottom MLP; sparse features -> distributed embedding
lookups (table-wise model parallel, DreamShard-placed) -> interaction ->
top MLP -> CTR logit.  Two interactions (``DLRMConfig.interaction``):

- ``"dot"``: the pairwise dot products of the dense representation and
  the pooled tables, with the dense representation beside them;
- ``"dcn"``: DCN-v2's low-rank cross network (Wang et al. 2021,
  arXiv:2008.13535) over x0 = concat(dense, pooled tables), as MLPerf's
  DLRM-DCNv2 runs it: ``x_{l+1} = x0 * (x_l V_l W_l + b_l) + x_l`` with
  V_l (F, rank) and W_l (rank, F), F = (tables + 1) * D; the top MLP
  takes the last x.

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
from repro.optim import RowWiseAdagrad, apply_updates
from repro.optim.optimizers import OptState

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
CROSS_SCOPE = "dlrm.cross.{}"             # cross layer l, in dlrm.interact
INTERACTIONS = ("dot", "dcn")


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    n_dense_features: int = 13
    embed_dim: int = 128            # padded feature dim (plan.dim)
    bottom_mlp: tuple = (512, 256)
    top_mlp: tuple = (1024, 512, 256)
    n_tables: int = 50
    interaction: str = "dot"        # one of INTERACTIONS
    cross_layers: int = 3           # dcn: number of cross layers
    cross_rank: int = 512           # dcn: low rank of V_l, W_l

    def __post_init__(self):
        if self.interaction not in INTERACTIONS:
            raise ValueError(f"interaction {self.interaction!r} not in "
                             f"{INTERACTIONS}")

    @property
    def dense_keys(self) -> tuple:
        """The parameters the dense optimizer trains."""
        if self.interaction == "dcn":
            return ("bottom", "cross", "top")
        return DENSE_PARAMS

    @property
    def top_in(self) -> int:
        """Width of the top MLP's input."""
        n = self.n_tables + 1               # tables + dense rep
        if self.interaction == "dcn":
            return n * self.embed_dim
        return n * (n - 1) // 2 + self.embed_dim


def _mlp_init(key, sizes, dtype):
    params = []
    for n_in, n_out in zip(sizes[:-1], sizes[1:]):
        key, k = jax.random.split(key)
        params.append({
            "w": (jax.random.normal(k, (n_in, n_out))
                  * np.sqrt(2.0 / n_in)).astype(dtype),
            "b": jnp.zeros((n_out,), dtype)})
    return params


def _cross_init(key, width, rank, n_layers, dtype):
    """Xavier-normal V (width, rank) and W (rank, width), zero bias."""
    std = np.sqrt(2.0 / (width + rank))
    out = []
    for i in range(n_layers):
        kv, kw = jax.random.split(jax.random.fold_in(key, i))
        out.append({
            "V": (jax.random.normal(kv, (width, rank)) * std).astype(dtype),
            "W": (jax.random.normal(kw, (rank, width)) * std).astype(dtype),
            "b": jnp.zeros((width,), dtype)})
    return out


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
        params = {
            "arenas": E.init_arenas(k1, self.plan, self.dtype),
            "bottom": _mlp_init(k2, (cfg.n_dense_features, *cfg.bottom_mlp,
                                     cfg.embed_dim), self.dtype),
            "top": _mlp_init(k3, (cfg.top_in, *cfg.top_mlp, 1), self.dtype),
        }
        if cfg.interaction == "dcn":
            params["cross"] = _cross_init(jax.random.fold_in(key, 3),
                                          cfg.top_in, cfg.cross_rank,
                                          cfg.cross_layers, self.dtype)
        return params

    def _interact(self, dense_rep, sparse):
        """Pairwise dot interaction. sparse: (B, T, D); dense: (B, D)."""
        feats = jnp.concatenate([dense_rep[:, None, :], sparse], axis=1)
        z = jnp.einsum("bid,bjd->bij", feats, feats)
        n = feats.shape[1]
        iu, ju = np.triu_indices(n, k=1)
        return jnp.concatenate([dense_rep, z[:, iu, ju]], axis=-1)

    def _cross(self, layers, dense_rep, sparse):
        """DCN-v2 low-rank cross network; each layer under its scope."""
        x0 = jnp.concatenate([dense_rep[:, None, :], sparse],
                             axis=1).reshape(dense_rep.shape[0], -1)
        x = x0
        for i, layer in enumerate(layers):
            with jax.named_scope(CROSS_SCOPE.format(i)):
                x = x0 * ((x @ layer["V"]) @ layer["W"] + layer["b"]) + x
        return x

    def embed(self, params, grouped_indices, lookup_fn):
        """(B, S*K, D) f32 pooled lookups in the plan's slot order."""
        with jax.named_scope(EMBED_SCOPE):
            bases = jnp.asarray(self.plan.base_rows)
            return lookup_fn(params["arenas"], bases, grouped_indices)

    def head(self, params, dense, sparse_all):
        """CTR logits (B,) from the dense features and the pooled lookups
        (``embed``); ``params`` needs only the dense parameters."""
        plan = self.plan
        with jax.named_scope(EMBED_SCOPE):
            # drop padded slots, keep true tables in original order
            order = plan.grouped_index_order()
            keep = np.flatnonzero(order >= 0)
            inv = keep[np.argsort(order[keep], kind="stable")]
            sparse = jnp.take(sparse_all, jnp.asarray(inv), axis=1)
        with jax.named_scope(BOTTOM_SCOPE):
            dense_rep = _mlp(params["bottom"], dense.astype(self.dtype))
        with jax.named_scope(INTERACT_SCOPE):
            if self.cfg.interaction == "dcn":
                x = self._cross(params["cross"], dense_rep,
                                sparse.astype(self.dtype))
            else:
                x = self._interact(dense_rep, sparse.astype(self.dtype))
        with jax.named_scope(TOP_SCOPE):
            return _mlp(params["top"], x)[:, 0]

    def forward(self, params, dense, grouped_indices, lookup_fn):
        """dense: (B, n_dense); grouped_indices: (B, S*K, P) (plan layout),
        or (B, S*W) for a plan with bag widths.

        lookup_fn: the sharded (or oracle) embedding lookup.
        Returns CTR logits (B,).
        """
        return self.head(params, dense,
                         self.embed(params, grouped_indices, lookup_fn))

    @staticmethod
    def loss(logits, labels):
        """Binary cross-entropy with logits."""
        with jax.named_scope(LOSS_SCOPE):
            logits = logits.astype(jnp.float32)
            return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                            + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def make_train_step(model: DLRM, lookup_fn, emb_opt, dense_opt):
    """One DLRM training step: ``emb_opt`` (row-wise) on the arenas and
    ``dense_opt`` on the dense parameters (``DLRMConfig.dense_keys``).

    Returns ``step(params, emb_state, dense_state, batch) -> (params,
    emb_state, dense_state, loss)``; ``batch`` holds ``"dense"`` (B, n_dense),
    ``"gidx"`` (B, S*K, P) plan-grouped indices (or (B, S*W) for a plan
    with bag widths) and ``"labels"`` (B,).

    With a ``repro.optim.RowWiseAdagrad`` the step differentiates the loss
    with respect to the pooled lookups, not the arenas, and updates the
    looked-up rows in place (``_row_update_step``); any other ``emb_opt``
    gets the arenas' dense gradient.
    """
    if isinstance(emb_opt, RowWiseAdagrad):
        return _row_update_step(model, lookup_fn, emb_opt, dense_opt)
    keys = model.cfg.dense_keys

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
                {k: g[k] for k in keys}, dense_state)
            dense = apply_updates({k: params[k] for k in keys}, du)
        return {**dense, **arenas}, emb_state, dense_state, loss

    return step


def _row_update_step(model: DLRM, lookup_fn, emb_opt: RowWiseAdagrad,
                     dense_opt):
    """The train step with row-wise Adagrad on the looked-up rows: the
    lookup runs forward only, the loss is differentiated with respect to
    its pooled output, and each shard's rows are updated in place from
    those gradients (``sharded.rowwise_adagrad_rows``), with no dense
    gradient of an arena."""
    if getattr(lookup_fn, "is_sharded", False):
        raise NotImplementedError(
            "the row update runs on the unsharded lookup; the sharded "
            "lookup takes an Optimizer such as rowwise_adagrad")
    plan, keys = model.plan, model.cfg.dense_keys

    def step(params, emb_state, dense_state, batch):
        dense = {k: params[k] for k in keys}
        pooled = model.embed(params, batch["gidx"], lookup_fn)

        def loss_fn(dense, pooled):
            logits = model.head(dense, batch["dense"], pooled)
            return DLRM.loss(logits, batch["labels"])

        loss, (g, g_pooled) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            dense, pooled)
        with jax.named_scope(EMB_UPDATE_SCOPE):
            arenas, accs = params["arenas"], emb_state.inner["arenas"]
            bases, K = jnp.asarray(plan.base_rows), plan.k_max
            for s in range(plan.n_shards):
                cols = None if plan.col_slot is None else plan.col_slot[s]
                arena, acc = E.rowwise_adagrad_rows(
                    arenas[s], accs[s], bases[s],
                    E.shard_indices(plan, batch["gidx"], s),
                    g_pooled[:, s * K:(s + 1) * K], lr=emb_opt.lr,
                    eps=emb_opt.eps, col_slot=cols)
                arenas, accs = arenas.at[s].set(arena), accs.at[s].set(acc)
            emb_state = OptState(emb_state.step + 1, {"arenas": accs})
        with jax.named_scope(DENSE_UPDATE_SCOPE):
            du, dense_state = dense_opt.update(g, dense_state)
            dense = apply_updates(dense, du)
        return {**dense, "arenas": arenas}, emb_state, dense_state, loss

    return step
