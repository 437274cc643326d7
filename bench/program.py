"""The system under test, as a step cell drives it.

Everything here calls the program (``repro``): the size-greedy placement
(``core.baselines.expert_place``), ``build_plan``, ``DLRM``,
``make_train_step``, the row-wise Adagrad and Adam optimizers, and the
lookups (``lookup_unsharded`` on one chip, ``make_sharded_lookup`` across
chips).  The benchmark adds only two named scopes from its own side, so
that the trace reduction can find the embedding layer's device ops:

- ``bench_emb_lookup`` around the lookup it hands to the step (its
  transpose is the embedding backward);
- ``bench_emb_update`` around the embedding optimizer's update.
"""

from __future__ import annotations

import dataclasses

import numpy as np

LOOKUP_SCOPE = "bench_emb_lookup"
UPDATE_SCOPE = "bench_emb_update"


@dataclasses.dataclass
class Program:
    plan: object
    model: object
    emb_opt: object
    dense_opt: object
    lookup: object
    mesh: object | None


def place(raw: np.ndarray, config: dict) -> np.ndarray:
    from repro.core.baselines import expert_place
    return expert_place(raw, config["n_shards"], config["capacity_gb"],
                        config["placement"])


def build(raw: np.ndarray, assignment: np.ndarray, config: dict,
          devices) -> Program:
    import jax
    import jax.numpy as jnp
    from repro.embedding import sharded as E
    from repro.embedding.plan import build_plan
    from repro.models.dlrm import DLRM, DLRMConfig
    from repro.optim import adam, rowwise_adagrad
    from repro.optim.optimizers import Optimizer

    plan = build_plan(raw, assignment, config["n_shards"],
                      pad_dim_to=config["embed_dim"])
    model = DLRM(DLRMConfig(n_dense_features=config["n_dense_features"],
                            embed_dim=plan.dim,
                            bottom_mlp=tuple(config["bottom_mlp"]),
                            top_mlp=tuple(config["top_mlp"]),
                            n_tables=config["n_tables"]),
                 plan, dtype=jnp.dtype(config["dtype"]))
    eo, do = config["emb_optimizer"], config["dense_optimizer"]
    inner = rowwise_adagrad(eo["lr"], eps=eo["eps"])

    def update(grads, state, params=None):
        with jax.named_scope(UPDATE_SCOPE):
            return inner.update(grads, state, params)

    emb_opt = Optimizer(inner.init, update)
    dense_opt = adam(do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"])

    mesh = None
    if config["lookup"] == "sharded":
        mesh = jax.make_mesh((1, config["n_shards"]), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2,
                             devices=devices[:config["n_shards"]])
        inner_lookup = E.make_sharded_lookup(mesh, plan)
    else:
        def inner_lookup(arenas, bases, gidx):
            return E.lookup_unsharded(arenas, plan.base_rows, gidx, plan)

    def lookup(arenas, bases, gidx):
        with jax.named_scope(LOOKUP_SCOPE):
            return inner_lookup(arenas, bases, gidx)

    return Program(plan, model, emb_opt, dense_opt, lookup, mesh)


def state_specs(prog: Program):
    """PartitionSpecs of (params, emb_state, dense_state, batch) on the
    ``(data, model)`` mesh, as ``chip_smoke.sharded_specs`` lays them out:
    arenas and their accumulators split over ``model``, dense nets
    replicated, dense features and labels split over both axes, and the
    indices whole on every shard."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.models.dlrm import DENSE_PARAMS
    from repro.optim.optimizers import OptState
    aparams = jax.eval_shape(prog.model.init_params, jax.random.PRNGKey(0))
    a_dense = jax.eval_shape(prog.dense_opt.init,
                             {k: aparams[k] for k in DENSE_PARAMS})

    def replicated(x):
        return P(*([None] * x.ndim))

    p_specs = {"arenas": P("model", None, None),
               **{k: jax.tree.map(replicated, aparams[k])
                  for k in DENSE_PARAMS}}
    e_specs = OptState(P(), {"arenas": P("model", None)})
    d_specs = jax.tree.map(replicated, a_dense)
    b_specs = {"dense": P(("data", "model"), None),
               "gidx": P("data", None, None),
               "labels": P(("data", "model"))}
    return p_specs, e_specs, d_specs, b_specs


def shardings(prog: Program, device):
    """(params, emb_state, dense_state, batch) shardings: NamedShardings
    on the mesh, or everything on ``device``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    specs = state_specs(prog)
    if prog.mesh is None:
        one = jax.sharding.SingleDeviceSharding(device)
        return jax.tree.map(lambda s: one, specs,
                            is_leaf=lambda s: isinstance(s, P))
    return jax.tree.map(lambda s: NamedSharding(prog.mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def make_step(prog: Program, shard):
    """The jitted train step with donated state, as users run it."""
    import jax
    from repro.models.dlrm import make_train_step
    fn = make_train_step(prog.model, prog.lookup, prog.emb_opt,
                         prog.dense_opt)
    loss_sharding = jax.tree.leaves(shard[0])[0]
    if prog.mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        loss_sharding = NamedSharding(prog.mesh, P())
    return jax.jit(fn, in_shardings=shard,
                   out_shardings=(*shard[:3], loss_sharding),
                   donate_argnums=(0, 1, 2))
