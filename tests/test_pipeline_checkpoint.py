"""Data pipeline determinism/seekability + checkpoint round-trips."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_pytree, save_pytree
from repro.data.pipeline import DLRMBatchStream, Prefetcher


def test_dlrm_stream_respects_hash_bounds(dlrm_pool):
    s = DLRMBatchStream(dlrm_pool[:6], batch=8, seed=0)
    b = s.batch_at(3)
    assert b["indices"].shape == (8, 6, 16)
    for t in range(6):
        live = b["indices"][:, t][b["indices"][:, t] >= 0]
        assert (live < dlrm_pool[t, 1]).all()


def test_prefetcher_matches_direct(dlrm_pool):
    s = DLRMBatchStream(dlrm_pool[:4], batch=2, seed=1)
    p = Prefetcher(s, depth=2)
    try:
        got = [p.next() for _ in range(3)]
    finally:
        p.close()
    for i, b in enumerate(got):
        want = s.batch_at(i)
        assert b.keys() == want.keys()
        for k in want:
            np.testing.assert_array_equal(b[k], want[k])


def test_checkpoint_roundtrip_mixed_dtypes():
    tree = {"a": jnp.ones((3, 4), jnp.bfloat16) * 1.5,
            "b": [jnp.arange(5), {"c": jnp.zeros((2,), jnp.float32)}],
            "step": jnp.asarray(7, jnp.int32)}
    with tempfile.TemporaryDirectory() as d:
        save_pytree(tree, os.path.join(d, "ckpt"))
        out = restore_pytree(jax.tree.map(jnp.zeros_like, tree),
                             os.path.join(d, "ckpt"))
    assert out["a"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out["a"], np.float32), 1.5)
    np.testing.assert_array_equal(out["b"][0], np.arange(5))
    assert int(out["step"]) == 7


def test_checkpoint_model_params_roundtrip(dlrm_pool):
    """The placed DLRM's parameters (bf16 arenas, f32 dense nets) and
    both optimizer states, after one step so that none is all zeros."""
    from repro.core import features as F
    from repro.embedding import sharded as E
    from repro.embedding.plan import build_plan
    from repro.models.dlrm import DLRM, DLRMConfig, make_train_step
    from repro.optim import adam, rowwise_adagrad
    raw = dlrm_pool[:6].copy()
    raw[:, F.HASH_SIZE] = np.clip(raw[:, F.HASH_SIZE], 0, 300)
    plan = build_plan(raw, np.arange(6) % 2, 2, pad_dim_to=16)
    model = DLRM(DLRMConfig(n_dense_features=4, embed_dim=plan.dim,
                            bottom_mlp=(8,), top_mlp=(8,), n_tables=6),
                 plan)
    params = model.init_params(jax.random.PRNGKey(0))
    params["arenas"] = params["arenas"].astype(jnp.bfloat16)
    emb_opt, dense_opt = rowwise_adagrad(0.05), adam(1e-3)
    step = make_train_step(
        model, lambda a, b, i: E.lookup_unsharded(a, b, i, plan), emb_opt,
        dense_opt)
    b = DLRMBatchStream(raw, batch=4, n_dense=4, pool_slots=3).batch_at(0)
    batch = {"dense": b["dense"], "labels": b["labels"],
             "gidx": E.group_indices(plan, b["indices"])}
    params, es, ds, _ = jax.jit(step)(
        params, emb_opt.init({"arenas": params["arenas"]}),
        dense_opt.init({k: params[k] for k in model.cfg.dense_keys}), batch)
    tree = {"params": params, "emb": es, "dense": ds}
    assert int(es.step) == int(ds.step) == 1
    assert params["arenas"].dtype == jnp.bfloat16
    with tempfile.TemporaryDirectory() as d:
        save_pytree(tree, os.path.join(d, "ckpt"))
        out = restore_pytree(jax.tree.map(jnp.zeros_like, tree),
                             os.path.join(d, "ckpt"))
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    for want, got in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_dreamshard_agent_checkpoint_roundtrip(dlrm_pool):
    from repro.core.trainer import DreamShard, DreamShardConfig
    from repro.data.tasks import make_benchmark_suite
    from repro.sim.costsim import CostSimulator
    sim = CostSimulator(seed=0)
    train, test = make_benchmark_suite(dlrm_pool, n_tables=10, n_devices=2,
                                       n_tasks=4)
    ds = DreamShard(train, sim, DreamShardConfig(n_iterations=1, n_cost=20,
                                                 n_rl=5))
    ds.train()
    a_before = ds.place(test[0].raw_features, 2)
    with tempfile.TemporaryDirectory() as d:
        ds.save(os.path.join(d, "agent"))
        ds2 = DreamShard(train, sim, ds.cfg)     # fresh (random) networks
        ds2.restore(os.path.join(d, "agent"))
    a_after = ds2.place(test[0].raw_features, 2)
    np.testing.assert_array_equal(a_before, a_after)
