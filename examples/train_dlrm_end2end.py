"""End-to-end driver: train a DLRM recommender for a few hundred steps on
CPU with the distributed table-parallel embedding path, comparing a
DreamShard placement against a random placement end to end.

The model is ~100M params at full table sizes; on CPU we shrink hash sizes
(CLI flags) while keeping the full pipeline: synthetic click-through data
-> ``Placer`` -> ``Placement`` (assignment + physical plan) -> sharded
embedding + dense MLPs -> row-wise Adagrad on arenas + Adam on the dense
nets.

  PYTHONPATH=src python examples/train_dlrm_end2end.py --steps 200
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import RandomPlacer, SimOracle
from repro.core import features as F
from repro.core.trainer import DreamShard, DreamShardConfig
from repro.data.synthetic import make_dlrm_pool
from repro.data.tasks import Task, make_benchmark_suite
from repro.embedding import sharded as E
from repro.models.dlrm import DLRM, DLRMConfig, make_train_step
from repro.optim import adam, rowwise_adagrad


def synth_batch(rng, plan, raw, batch, n_dense, pool_max=6):
    """Synthetic CTR batch: zipf-ish indices per table + dense features."""
    M = raw.shape[0]
    hashes = raw[:, F.HASH_SIZE].astype(np.int64)
    pools = np.minimum(raw[:, F.POOLING].astype(np.int64) + 1, pool_max)
    idx = np.full((batch, M, pool_max), -1, np.int32)
    for t in range(M):
        draws = rng.zipf(1.5, size=(batch, pools[t])) % hashes[t]
        idx[:, t, :pools[t]] = draws
    dense = rng.normal(size=(batch, n_dense)).astype(np.float32)
    labels = (rng.random(batch) < 0.3).astype(np.float32)
    return {"gidx": jnp.asarray(E.group_indices(plan, idx)),
            "dense": jnp.asarray(dense), "labels": jnp.asarray(labels)}


def train_with_placement(name, task, placement, args, oracle):
    plan = placement.plan                     # physical layout, ready-made
    raw = task.raw_features
    cost = oracle.evaluate(raw, placement.assignment,
                           placement.n_devices).overall
    cfg = DLRMConfig(n_dense_features=13, embed_dim=plan.dim,
                     bottom_mlp=(128, 64), top_mlp=(256, 128, 64),
                     n_tables=raw.shape[0])
    model = DLRM(cfg, plan)
    params = model.init_params(jax.random.PRNGKey(0))
    n_params = sum(np.prod(p.shape) for p in jax.tree.leaves(params))

    emb_opt = rowwise_adagrad(0.05)
    dense_opt = adam(1e-3)
    emb_state = emb_opt.init({"arenas": params["arenas"]})
    dense_state = dense_opt.init({k: params[k] for k in ("bottom", "top")})
    def lookup(a, b, i):
        return E.lookup_unsharded(a, plan.base_rows, i, plan)

    step = jax.jit(make_train_step(model, lookup, emb_opt, dense_opt))

    rng = np.random.default_rng(0)
    losses, t0 = [], time.perf_counter()
    for i in range(args.steps):
        batch = synth_batch(rng, plan, raw, args.batch, 13)
        params, emb_state, dense_state, loss = step(
            params, emb_state, dense_state, batch)
        losses.append(float(loss))
        if i % max(args.steps // 5, 1) == 0:
            print(f"  [{name}] step {i:4d} loss {np.mean(losses[-20:]):.4f}")
    wall = time.perf_counter() - t0
    print(f"  [{name}] {n_params / 1e6:.1f}M params, "
          f"placement cost {cost:.2f} ms/iter (simulated), "
          f"final loss {np.mean(losses[-20:]):.4f}, wall {wall:.1f}s")
    assert np.isfinite(losses).all()
    return cost, losses[-1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--tables", type=int, default=24)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--max-rows", type=int, default=20000)
    args = ap.parse_args()

    pool = make_dlrm_pool(seed=0)
    oracle = SimOracle(seed=0)
    raw = pool[: args.tables].copy()
    raw[:, F.HASH_SIZE] = np.clip(raw[:, F.HASH_SIZE], 100, args.max_rows)
    raw[:, F.TABLE_SIZE_GB] = F.table_size_gb(raw[:, F.DIM],
                                              raw[:, F.HASH_SIZE])
    task = Task.of(raw, args.shards, name="dlrm-end2end")

    print("training DreamShard placer (small budget)...")
    train_tasks, _ = make_benchmark_suite(pool, args.tables, args.shards,
                                          n_tasks=8)
    agent = DreamShard(train_tasks, oracle,
                       DreamShardConfig(n_iterations=5, n_cost=150, n_rl=10))
    agent.train()
    ds_placement = agent.as_placer().place(task)
    rnd_placement = RandomPlacer(oracle, seed=0).place(task)

    print("\n== DLRM end-to-end with DreamShard placement ==")
    c1, _ = train_with_placement("dreamshard", task, ds_placement, args,
                                 oracle)
    print("== DLRM end-to-end with random placement ==")
    c2, _ = train_with_placement("random", task, rnd_placement, args, oracle)
    print(f"\nembedding step cost: dreamshard {c1:.2f} ms vs random "
          f"{c2:.2f} ms  ({(c2 / c1 - 1) * 100:+.1f}%)")


if __name__ == "__main__":
    main()
