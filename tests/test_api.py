"""Unified placement API: Placer adapters vs legacy call paths, oracle
caching, batched PlacementSession parity, and PlacementPlan edge cases."""

import numpy as np
import pytest

from repro.api import (CachedOracle, CostOracle, DreamShardPlacer,
                       ExpertPlacer, KernelOracle, Placement, PlacementSession,
                       Placer, RNNPlacerAdapter, RandomPlacer, SimOracle,
                       ensure_oracle, make_baseline_placers)
from repro.core import baselines as B
from repro.core.trainer import DreamShard, DreamShardConfig
from repro.data.tasks import sample_tasks, split_pool
from repro.embedding.plan import build_plan
from repro.sim.costsim import CostSimulator, placement_digest


@pytest.fixture(scope="module")
def suite(dlrm_pool):
    """Small heterogeneous suite (two table counts) + an untrained agent."""
    _, test_ids = split_pool(dlrm_pool, seed=0)
    tasks = (sample_tasks(dlrm_pool, test_ids, 8, 2, 2, seed=1, name="s8")
             + sample_tasks(dlrm_pool, test_ids, 11, 2, 2, seed=2, name="s11"))
    sim = CostSimulator(seed=0)
    agent = DreamShard(tasks, sim, DreamShardConfig(n_iterations=1))
    return tasks, sim, agent


# ---- oracles -----------------------------------------------------------------

def test_ensure_oracle_wraps_simulator(sim):
    oracle = ensure_oracle(sim)
    assert isinstance(oracle, SimOracle)
    assert oracle.mem_capacity_gb == sim.spec.mem_capacity_gb
    assert ensure_oracle(oracle) is oracle          # idempotent
    with pytest.raises(TypeError):
        ensure_oracle(object())


def test_sim_oracle_counts_evaluations(dlrm_pool, sim):
    oracle = SimOracle(sim)
    a = np.array([0, 1, 0, 1])
    before = oracle.num_evaluations
    oracle.evaluate(dlrm_pool[:4], a, 2)
    assert oracle.num_evaluations == before + 1 == sim.num_evaluations


def test_placement_digest_deterministic(dlrm_pool):
    a = np.array([0, 1, 0, 1, 2, 2])
    d1 = placement_digest(dlrm_pool[:6], a, 4)
    d2 = placement_digest(dlrm_pool[:6].copy(), a.copy(), 4)
    assert d1 == d2
    assert d1 != placement_digest(dlrm_pool[:6], a, 2)          # device count
    assert d1 != placement_digest(dlrm_pool[1:7], a, 4)         # raw features
    a2 = a.copy()
    a2[0] = 1
    assert d1 != placement_digest(dlrm_pool[:6], a2, 4)         # assignment


def test_sim_noise_keyed_on_digest(dlrm_pool):
    """Same placement -> identical measurement across simulator instances."""
    a = np.array([0, 1, 0, 1])
    r1 = CostSimulator(seed=3).evaluate(dlrm_pool[:4], a, 2)
    r2 = CostSimulator(seed=3).evaluate(dlrm_pool[:4], a, 2)
    assert r1.overall == r2.overall


def test_cached_oracle_hit_miss_counting(dlrm_pool, sim):
    oracle = CachedOracle(sim)
    a = np.array([0, 1, 0, 1])
    r1 = oracle.evaluate(dlrm_pool[:4], a, 2)
    r2 = oracle.evaluate(dlrm_pool[:4], a, 2)
    assert (oracle.hits, oracle.misses) == (1, 1)
    assert r1.overall == r2.overall
    assert oracle.num_evaluations == 1              # hits consume no budget
    oracle.evaluate(dlrm_pool[:4], np.array([1, 0, 1, 0]), 2)   # new placement
    oracle.evaluate(dlrm_pool[2:6], a, 2)                       # new tables
    assert (oracle.hits, oracle.misses) == (1, 3)
    assert oracle.num_evaluations == 3


def test_cached_oracle_lru_eviction_and_counters(dlrm_pool, sim, telemetry):
    from repro import telemetry as tele
    oracle = CachedOracle(sim, max_entries=2)
    a1, a2, a3 = (np.array(x) for x in
                  ([0, 1, 0, 1], [1, 0, 1, 0], [0, 0, 1, 1]))
    oracle.evaluate(dlrm_pool[:4], a1, 2)
    oracle.evaluate(dlrm_pool[:4], a2, 2)
    oracle.evaluate(dlrm_pool[:4], a1, 2)       # hit: a1 becomes most-recent
    oracle.evaluate(dlrm_pool[:4], a3, 2)       # full: evicts a2, NOT a1
    oracle.evaluate(dlrm_pool[:4], a1, 2)       # still cached (LRU, not FIFO)
    assert oracle.num_evaluations == 3
    oracle.evaluate(dlrm_pool[:4], a2, 2)       # evicted -> re-measured
    assert oracle.num_evaluations == 4
    assert (oracle.hits, oracle.misses) == (2, 4)
    assert oracle.evictions == 2
    # the same accounting streams through process-wide telemetry
    counters = tele.snapshot()["counters"]
    assert counters["oracle.cache.hits"] == 2
    assert counters["oracle.cache.misses"] == 4


def test_cached_oracle_info_is_removed(sim):
    """The deprecated ``info()`` shim is gone: the supported surfaces
    are the instance counters + ``telemetry.snapshot()``, and the error
    says so."""
    with pytest.raises(AttributeError, match=r"telemetry\.snapshot"):
        CachedOracle(sim).info()
    with pytest.raises(AttributeError, match="no attribute"):
        CachedOracle(sim).nonexistent_attr


def test_costsim_comm_ms_alias_is_removed():
    """The private ``_comm_ms`` alias is gone; the error points at the
    public ``comm_ms`` name."""
    from repro.sim.costsim import CostSimulator
    with pytest.raises(AttributeError, match="comm_ms"):
        CostSimulator()._comm_ms


def test_kernel_oracle_smoke(dlrm_pool):
    oracle = KernelOracle(batch_size=8, pooling=2, max_rows=256, repeats=1)
    assert isinstance(oracle, CostOracle)
    res = oracle.evaluate(dlrm_pool[:4], np.array([0, 1, 0, 1]), 2)
    assert oracle.num_evaluations == 1
    assert np.isfinite(res.overall) and res.overall > 0
    assert res.fwd_comp.shape == (2,) and (res.fwd_comp > 0).all()
    assert res.cost_features.shape == (2, 3)


# ---- placer adapters vs legacy call paths ------------------------------------

def test_expert_placer_matches_legacy(suite):
    tasks, sim, _ = suite
    for s in B.EXPERT_STRATEGIES:
        placer = ExpertPlacer(sim, s)
        for t in tasks:
            legacy = B.expert_place(t.raw_features, t.n_devices,
                                    sim.spec.mem_capacity_gb, s)
            p = placer.place(t)
            np.testing.assert_array_equal(p.assignment, legacy)
            assert p.strategy == s and p.oracle_evals == 0


def test_random_placer_matches_legacy(suite):
    tasks, sim, _ = suite
    placer = RandomPlacer(sim, seed=42)
    rng = np.random.default_rng(42)
    for t in tasks:           # shared stream, like the legacy helper
        legacy = B.random_place(t.raw_features, t.n_devices,
                                sim.spec.mem_capacity_gb, rng)
        np.testing.assert_array_equal(placer.place(t).assignment, legacy)


def test_dreamshard_placer_matches_legacy(suite):
    tasks, _, agent = suite
    placer = agent.as_placer()
    assert isinstance(placer, Placer)
    t = tasks[0]
    p = placer.place(t)
    np.testing.assert_array_equal(
        p.assignment, agent.place(t.raw_features, t.n_devices))
    assert p.strategy == "dreamshard"
    assert p.candidates == agent.cfg.inference_candidates
    assert p.est_cost_ms is not None and np.isfinite(p.est_cost_ms)


def test_rnn_placer_adapter_matches_legacy(suite):
    from repro.core.rnn_policy import RNNPlacer, RNNPolicyConfig
    tasks, sim, _ = suite
    rnn = RNNPlacer(tasks, sim, RNNPolicyConfig(n_updates=1))  # untrained
    adapter = rnn.as_placer()
    assert isinstance(adapter, RNNPlacerAdapter)
    t = tasks[0]
    np.testing.assert_array_equal(
        adapter.place(t).assignment, rnn.place(t.raw_features, t.n_devices))


def test_placement_carries_plan(suite):
    tasks, sim, _ = suite
    p = ExpertPlacer(sim, "size").place(tasks[0])
    assert isinstance(p, Placement)
    np.testing.assert_array_equal(p.plan.assignment, p.assignment)
    assert p.plan.n_shards == tasks[0].n_devices
    assert p.n_tables == tasks[0].n_tables


def test_make_baseline_placers_all_legal(suite):
    tasks, sim, _ = suite
    placers = make_baseline_placers(sim, seed=0)
    assert set(placers) == {"random", *B.EXPERT_STRATEGIES}
    for placer in placers.values():
        for p, t in zip(placer.place_many(tasks), tasks):
            assert sim.legal(t.raw_features, p.assignment, t.n_devices)


# ---- batched PlacementSession ------------------------------------------------

def test_session_matches_per_task_place(suite):
    """Bucketed, padded, vmapped decoding == per-task Algorithm 2."""
    tasks, _, agent = suite
    session = PlacementSession(agent, bucket_tables=8)
    placements = session.place_many(tasks)
    for t, p in zip(tasks, placements):
        np.testing.assert_array_equal(
            p.assignment, agent.place(t.raw_features, t.n_devices))
        assert p.assignment.shape == (t.n_tables,)


def test_session_compiles_once_per_bucket(suite):
    tasks, _, agent = suite
    session = PlacementSession(agent, bucket_tables=8)
    # table counts 8 and 11 pad to different 8-multiples -> 2 buckets
    assert {session.bucket_key(t) for t in tasks} == {(8, 2), (16, 2)}
    session.place_many(tasks)
    assert session.num_compiles == 2
    session.place_many(tasks)                     # warm: no new traces
    assert session.num_compiles == 2
    assert session.num_decode_calls == 4


def test_session_no_retrace_across_batch_sizes(suite):
    """Batch dim pads to a power of two: 1-task and 2-task calls into the
    same bucket share one trace; a 3rd distinct (bucket, b_pad) traces."""
    tasks, _, agent = suite
    same_bucket = [t for t in tasks if t.n_tables == 8]
    session = PlacementSession(agent, bucket_tables=8)
    p1 = session.place(same_bucket[0])                 # b_pad = 1
    assert session.num_compiles == 1
    p1b = session.place(same_bucket[1])                # same shapes
    assert session.num_compiles == 1
    both = session.place_many(same_bucket)             # b_pad = 2: new trace
    assert session.num_compiles == 2
    np.testing.assert_array_equal(p1.assignment, both[0].assignment)
    np.testing.assert_array_equal(p1b.assignment, both[1].assignment)


def test_session_bucket_reuse_across_interleaved_batches(
        suite, dlrm_pool, telemetry):
    """Interleaved ``place_many`` calls over mixed (M, D) shapes reuse
    per-bucket traces: one compile per distinct (M_pad, D, b_pad)
    regardless of call order, observable via ``session.bucket_compiles``."""
    from repro import telemetry as tele
    _, _, agent = suite
    _, test_ids = split_pool(dlrm_pool, seed=0)
    t8a = sample_tasks(dlrm_pool, test_ids, 8, 2, 2, seed=11)
    t8b = sample_tasks(dlrm_pool, test_ids, 8, 2, 2, seed=12)
    t11 = sample_tasks(dlrm_pool, test_ids, 11, 2, 2, seed=13)
    t8d4 = sample_tasks(dlrm_pool, test_ids, 8, 4, 2, seed=14)
    session = PlacementSession(agent, bucket_tables=8)
    session.place_many(t8a + t11)         # cold: (8, 2) and (16, 2) buckets
    assert session.num_compiles == 2
    assert tele.counter_value("session.bucket_compiles") == 2
    session.place_many(t11 + t8b)         # interleaved revisit: no retrace
    assert session.num_compiles == 2
    session.place_many(t8d4)              # new D -> exactly one new trace
    assert session.num_compiles == 3
    session.place_many(t8b + t8d4 + t11)  # all-warm mixed batch: no retrace
    assert session.num_compiles == 3
    assert tele.counter_value("session.bucket_compiles") == 3


def test_session_decode_span_lasts_until_the_host_holds_the_result(
        suite, telemetry):
    """``session.decode`` ends when the decode's result is on the host:
    a result that becomes ready only after a delay gives a span at least
    that long."""
    import time
    delay = 0.25
    tasks, _, agent = suite
    session = PlacementSession(agent, bucket_tables=8)
    session.place(tasks[0])                        # compile outside

    class Late:
        """An array the host can read only ``delay`` seconds on."""

        def __init__(self, value):
            self.value = value

        def __array__(self, dtype=None, copy=None):
            time.sleep(delay)
            return np.asarray(self.value, dtype=dtype)

    decode_fn = session._decode_fn

    def late_fn(*key):
        fn = decode_fn(*key)
        return lambda *args: tuple(map(Late, fn(*args)))

    session._decode_fn = late_fn
    telemetry.reset()
    placed = session.place(tasks[0])
    np.testing.assert_array_equal(
        placed.assignment, agent.place(tasks[0].raw_features,
                                       tasks[0].n_devices))
    (decode,) = [e for e in telemetry.get_tracer().snapshot_events()
                 if e[0] == "session.decode"]
    assert decode[2] >= 2 * delay * 1e6            # two reads, dur in us
    assert decode[6]["fresh_compile"] is False
    assert not [e for e in telemetry.get_tracer().snapshot_events()
                if e[0] == "session.compile"]


def test_session_estimates_match_per_task(suite):
    tasks, _, agent = suite
    session = PlacementSession(agent)
    p = session.place(tasks[0])
    _, est = agent.place_detailed(tasks[0].raw_features,
                                  tasks[0].n_devices)
    assert p.est_cost_ms == pytest.approx(est, rel=1e-5)


def test_dreamshard_placer_place_many_uses_session(suite):
    tasks, _, agent = suite
    placer = DreamShardPlacer(agent)
    placements = placer.place_many(tasks)
    assert placer.session.num_decode_calls >= 1
    assert len(placements) == len(tasks)


# ---- PlacementPlan edge cases ------------------------------------------------

def test_plan_empty_shard(dlrm_pool):
    """A device with no tables still gets a (padded) group."""
    raw = dlrm_pool[:5]
    assignment = np.array([0, 0, 2, 2, 2])        # shard 1 empty
    plan = build_plan(raw, assignment, 3)
    assert len(plan.groups[1]) == 0
    assert (plan.slot_table[1] == -1).all()
    assert (plan.base_rows[1] == 0).all()         # pad slots hit the zero row
    order = plan.grouped_index_order()
    assert order.shape == (3 * plan.k_max,)
    live = order[order >= 0]
    assert sorted(live.tolist()) == list(range(5))   # every table exactly once


def test_plan_pad_slots_in_grouped_order(dlrm_pool):
    raw = dlrm_pool[:7]
    assignment = np.array([0, 1, 0, 1, 0, 1, 0])  # 4 vs 3 tables
    plan = build_plan(raw, assignment, 2)
    assert plan.k_max == 4
    order = plan.grouped_index_order()
    assert (order == -1).sum() == 1               # one pad slot on shard 1
    assert order[plan.k_max + 3] == -1            # trailing slot of shard 1
    live = order[order >= 0]
    assert sorted(live.tolist()) == list(range(7))


def test_plan_single_shard_roundtrip(dlrm_pool):
    raw = dlrm_pool[:4]
    plan = build_plan(raw, np.zeros(4, np.int64), 1)
    assert plan.k_max == 4 and plan.n_shards == 1
    assert plan.rows_max == 1 + int(plan.table_rows.sum())


# ---- trainer integration -----------------------------------------------------

def test_trainer_accepts_oracle_and_sim(suite):
    tasks, _, _ = suite
    sim = CostSimulator(seed=0)
    via_sim = DreamShard(tasks, sim, DreamShardConfig(n_iterations=1))
    via_oracle = DreamShard(tasks, SimOracle(CostSimulator(seed=0)),
                            DreamShardConfig(n_iterations=1))
    assert via_sim.oracle.mem_capacity_gb == via_oracle.oracle.mem_capacity_gb
    assert via_sim.sim is sim                      # legacy alias


def test_restore_rebuilds_cached_placer(suite, tmp_path):
    """restore() must drop the cached PlacementSession: its candidate count
    was frozen from the pre-restore config."""
    tasks, _, _ = suite
    saved = DreamShard(tasks, CostSimulator(seed=0),
                       DreamShardConfig(n_iterations=1,
                                        inference_candidates=4))
    saved.save(str(tmp_path / "agent"))
    agent = DreamShard(tasks, CostSimulator(seed=0),
                       DreamShardConfig(n_iterations=1))
    stale = agent.as_placer()
    assert stale.session.n_candidates == 16              # default config
    agent.restore(str(tmp_path / "agent"))
    fresh = agent.as_placer()
    assert fresh is not stale
    assert fresh.session.n_candidates == 4               # restored config


def test_trainer_with_cached_oracle_collects(suite):
    tasks, _, _ = suite
    cached = CachedOracle(CostSimulator(seed=0))
    ds = DreamShard(tasks, cached,
                    DreamShardConfig(n_iterations=1, n_collect=3, n_cost=2,
                                     n_rl=1))
    ds.collect()
    assert cached.hits + cached.misses == 3


# ---- repro.api export surface ------------------------------------------------

def test_api_all_exports_resolve():
    """__all__ is sorted and deduped, every name (lazy registry
    included) resolves, and every lazy name is both exported and
    actually defined by its source module."""
    import importlib

    import repro.api as api
    assert api.__all__ == sorted(set(api.__all__))
    for name in api.__all__:
        assert getattr(api, name) is not None, name
    assert set(api._LAZY) <= set(api.__all__)
    for name, module in api._LAZY.items():
        assert getattr(importlib.import_module(module), name) \
            is getattr(api, name), name
    assert dir(api) == sorted(api.__all__)
    with pytest.raises(AttributeError, match="not_a_real_export"):
        api.not_a_real_export
