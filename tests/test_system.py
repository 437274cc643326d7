"""End-to-end system behaviour: the full DreamShard pipeline on the
synthetic DLRM pool reproduces the paper's qualitative results at reduced
budget."""

import numpy as np

from repro.core import baselines as B
from repro.core.trainer import DreamShard, DreamShardConfig
from repro.data.tasks import make_benchmark_suite
from repro.sim.costsim import CostSimulator


def test_dreamshard_pipeline_beats_every_baseline_on_average(dlrm_pool):
    """Reduced-budget version of Table 1 (one task size)."""
    sim = CostSimulator(seed=0)
    train, test = make_benchmark_suite(dlrm_pool, n_tables=20, n_devices=4,
                                       n_tasks=12)
    ds = DreamShard(train, sim, DreamShardConfig(n_iterations=6, n_cost=150,
                                                 n_rl=10))
    ds.train()
    ours = ds.evaluate_tasks(test)
    rng = np.random.default_rng(0)
    scores = {"random": np.mean([sim.evaluate(
        t.raw_features, B.random_place(t.raw_features, 4,
                                       sim.spec.mem_capacity_gb, rng),
        4).overall for t in test])}
    for s in B.EXPERT_STRATEGIES:
        scores[s] = np.mean([sim.evaluate(
            t.raw_features, B.expert_place(t.raw_features, 4,
                                           sim.spec.mem_capacity_gb, s),
            4).overall for t in test])
    # must beat random clearly and be at least competitive with the best
    # expert (within 3%; usually better)
    assert ours < scores["random"] * 0.9
    assert ours < min(scores.values()) * 1.03, (ours, scores)


def test_estimated_mdp_saves_measurements(dlrm_pool):
    """Fig 8 mechanism: training touches hardware only N_collect times per
    iteration regardless of RL update volume."""
    sim = CostSimulator(seed=0)
    train, _ = make_benchmark_suite(dlrm_pool, n_tables=10, n_devices=2,
                                    n_tasks=4)
    cfg = DreamShardConfig(n_iterations=2, n_collect=5, n_cost=20, n_rl=30,
                           n_episode=10)
    ds = DreamShard(train, sim, cfg)
    ds.train()
    # 2 iterations x 5 collects = 10 measurements; the 600 RL episodes were
    # free (estimated MDP)
    assert sim.num_evaluations == 10


def test_inference_needs_no_measurements(dlrm_pool):
    sim = CostSimulator(seed=0)
    train, test = make_benchmark_suite(dlrm_pool, n_tables=10, n_devices=2,
                                       n_tasks=4)
    ds = DreamShard(train, sim, DreamShardConfig(n_iterations=1, n_cost=20,
                                                 n_rl=5))
    ds.train()
    before = sim.num_evaluations
    ds.place(test[0].raw_features, 2)
    assert sim.num_evaluations == before        # Algorithm 2: no hardware


def test_ablation_without_cost_features_runs(dlrm_pool):
    sim = CostSimulator(seed=0)
    train, _ = make_benchmark_suite(dlrm_pool, n_tables=10, n_devices=2,
                                    n_tasks=4)
    cfg = DreamShardConfig(n_iterations=1, n_cost=20, n_rl=5,
                           use_cost_features=False)
    ds = DreamShard(train, sim, cfg)
    ds.train()
    a = ds.place(train[0].raw_features, 2)
    assert a.shape == (10,)


def test_feature_drop_ablation_runs(dlrm_pool):
    sim = CostSimulator(seed=0)
    train, _ = make_benchmark_suite(dlrm_pool, n_tables=10, n_devices=2,
                                    n_tasks=4)
    cfg = DreamShardConfig(n_iterations=1, n_cost=20, n_rl=5,
                           feature_drop="pooling")
    ds = DreamShard(train, sim, cfg)
    ds.train()
    assert ds.place(train[0].raw_features, 2).shape == (10,)

