"""Bring-up smoke run: the placement system and the placed DLRM step on TPU.

    python chip_smoke.py               # one chip: phases 0-4
    python chip_smoke.py --four-chips  # four chips: the sharded path only

Phases (one process; each prints one line, any failure exits non-zero):

  0. device and compile cache -- exits non-zero at once without a TPU;
  1. DreamShard agent training (fused trainer, default widths), 2
     iterations on DLRM-50 (4) tasks;
  2. ``PlacementService`` in front of that agent answers 16 requests;
  3. the Pallas embedding-bag kernel, compiled, at the largest shard of
     the phase-4 plan (bf16, 128 lanes, batch 65536, 16 slots), against
     ``embedding_bag_ref``;
  4. 5 steps of the placed DLRM train step: DLRM-50 at full hash sizes,
     placed on 4 placement shards by the phase-1 agent, ``FULL`` dense
     widths, batch 65536, all shards on one chip through
     ``lookup_unsharded``; pooled lookups checked against NumPy.

``--four-chips`` instead runs the plan's shards one per chip through
``make_sharded_lookup``, compares it with ``lookup_unsharded``, takes 3
sharded train steps and measures the all-to-all.  The plan comes from the
phase-1 agent, so that phase runs first.

The last line printed is ``{"ok": true, "device": {...}}``.  Times printed
here are smoke times of single runs, not benchmarks.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_TABLES = 50             # DLRM-50
N_SHARDS = 4              # placement devices of the task
SLOTS = 16                # pooling slots per table (as in launch/dryrun.py)
SEED = 0


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---- phase 0 ------------------------------------------------------------


def check_device(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[0 device] FAIL: JAX found no TPU "
              f"(platform={devs[0].platform})", file=sys.stderr)
        sys.exit(2)
    if len(devs) < n_chips:
        print(f"[0 device] FAIL: need {n_chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    say("0 device", f"platform={devs[0].platform} kind={devs[0].device_kind}"
        f" count={len(devs)} jax={jax.__version__} compile_cache={cache}")
    return devs


def report_comm(devices) -> None:
    """The all-to-all model the profiling path would use on ``devices``:
    measured on several chips, synthetic (from the named spec's analytic
    constants, not a measurement) on one."""
    from repro.profiling.collectives import calibrate_comm
    from repro.sim.hardware import TPU_V5E
    comm = calibrate_comm(TPU_V5E, devices=devices)
    assert comm.source == ("measured" if len(devices) > 1 else "synthetic")
    say("comm", f"calibrate_comm on {len(devices)} chip(s): source="
        f"{comm.source} alpha_ms={comm.alpha_ms} beta_ms_per_mb="
        f"{comm.beta_ms_per_mb}" + ("" if comm.source == "measured" else
                                     f" (from {TPU_V5E.name} constants)"))


# ---- phase 1 ------------------------------------------------------------


def train_agent(pool, n_iterations: int = 2):
    from repro.api import SimOracle
    from repro.core.trainer import DreamShard, DreamShardConfig
    from repro.data.tasks import make_benchmark_suite
    train, _ = make_benchmark_suite(pool, N_TABLES, N_SHARDS, n_tasks=8)
    agent = DreamShard(train, SimOracle(seed=SEED),
                       DreamShardConfig(n_iterations=n_iterations,
                                        fused=True))
    t0 = time.perf_counter()
    agent.train()
    wall = time.perf_counter() - t0
    for h in agent.history:
        assert np.isfinite(h["cost_loss"]), h
        assert np.isfinite(h["mean_est_reward"]), h
    traces = (agent._fused_cost_update.traces[0]
              + agent._fused_rl_update.traces[0])
    last = agent.history[-1]
    say("1 agent", f"ok: {n_iterations} iterations on {len(train)} "
        f"DLRM-{N_TABLES} ({N_SHARDS}) tasks, cost_loss={last['cost_loss']}"
        f" est_reward={last['mean_est_reward']} compiled_traces={traces}"
        f" wall_s={wall}")
    return agent


# ---- phase 2 ------------------------------------------------------------


def serve(agent, pool, n_requests: int = 16):
    from repro.api import PlacementService, legal_sharded
    from repro.data.traffic import TrafficConfig, make_trace
    trace = make_trace(pool, TrafficConfig(n_tables=N_TABLES,
                                           n_devices=N_SHARDS,
                                           n_requests=n_requests, seed=SEED))
    svc = PlacementService(agent)
    served = []
    t0 = time.perf_counter()
    for i, r in enumerate(trace):
        served += svc.submit(r.raw_features, r.n_devices, tag=i)
    served += svc.flush()
    wall = time.perf_counter() - t0
    assert sorted(s.tag for s in served) == list(range(n_requests)), \
        [s.tag for s in served]
    for s in served:
        assert s.error is None and s.placement is not None, s
        raw, p = trace[s.tag].raw_features, s.placement
        if p.is_sharded:
            legal = legal_sharded(svc.oracle, raw, p.sharding,
                                  p.shard_assignment[None], p.n_devices)[0]
        else:
            legal = svc.oracle.legal(raw, p.assignment, p.n_devices)
        assert legal, f"request {s.tag}: illegal placement"
    sources = dict(collections.Counter(s.source for s in served))
    say("2 serve", f"ok: {len(served)}/{n_requests} requests served, all "
        f"legal, sources={sources} decode_batches={svc.decode_batches} "
        f"wall_s={wall}")


# ---- shared set-up of phases 3-5 ------------------------------------------


def placed_plan(agent, pool):
    """DLRM-50 at full hash sizes, placed by ``agent``; the step's layout:
    native dim 16 (``pad_dim_to=16``)."""
    from repro.data.tasks import Task
    from repro.embedding.plan import build_plan
    task = Task.of(pool[:N_TABLES], N_SHARDS, name=f"DLRM-{N_TABLES}")
    placement = agent.as_placer().place(task)
    plan = build_plan(task.raw_features, placement.assignment, N_SHARDS,
                      pad_dim_to=16)
    return task.raw_features, plan


def shard_rows(plan) -> np.ndarray:
    """Arena rows in use per shard (zero row included)."""
    live = plan.slot_table >= 0
    rows = np.where(live, plan.table_rows[np.maximum(plan.slot_table, 0)], 0)
    return 1 + rows.sum(axis=1)


def make_batch(key, plan, raw, batch: int, slots: int = SLOTS):
    """One synthetic CTR batch on the device: per-table uniform row ids,
    each table's own pooling factor (capped at ``slots``) then -1
    padding, grouped in the plan's (B, S*K, slots) slot layout."""
    import jax
    import jax.numpy as jnp
    from repro.core import features as F
    order = plan.grouped_index_order()
    live = order >= 0
    owner = np.maximum(order, 0)
    rows = np.where(live, plan.table_rows[owner], 1).astype(np.int32)
    pools = np.clip(np.rint(raw[owner, F.POOLING]), 1, slots)
    pools = np.where(live, pools, 0).astype(np.int32)

    @jax.jit
    def build(key):
        k1, k2, k3 = jax.random.split(key, 3)
        idx = jax.random.randint(k1, (batch, order.shape[0], slots), 0,
                                 rows[None, :, None], jnp.int32)
        slot = jnp.arange(slots)[None, None, :]
        idx = jnp.where(slot < pools[None, :, None], idx, -1)
        dense = jax.random.normal(k2, (batch, 13), jnp.float32)
        labels = jax.random.bernoulli(k3, 0.3, (batch,)).astype(jnp.float32)
        return {"dense": dense, "gidx": idx, "labels": labels}

    return build(key)


# ---- phase 3 ------------------------------------------------------------


def assert_compiled_kernel(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text(), \
        "embedding_bag_fused did not lower to a Mosaic kernel"


def kernel_phase(plan, gidx, dim: int = 128):
    """The Pallas kernel, compiled, on the largest shard of ``plan``: its
    tables padded to ``dim`` lanes in bf16, the batch's own lookups."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.embedding_bag.kernel import embedding_bag_fused
    from repro.kernels.embedding_bag.ref import embedding_bag_ref

    per_shard = shard_rows(plan)
    s = int(np.argmax(per_shard))
    rows = int(per_shard[s])
    k = int((plan.slot_table[s] >= 0).sum())       # live slots lead
    B = gidx.shape[0]
    bases = jnp.asarray(plan.base_rows[s, :k], jnp.int32)

    @jax.jit
    def inputs(key, gidx):
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, dim), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, dim), 0)
        w = jax.random.normal(key, (rows, dim), jnp.bfloat16) * 0.1
        arena = jnp.where((col < plan.dim) & (row > 0), w, 0)
        idx = gidx[:, s * plan.k_max:s * plan.k_max + k]
        flat = jnp.where(idx >= 0, idx + bases[None, :, None], 0)
        return arena.astype(jnp.bfloat16), flat.reshape(B * k, -1)

    arena, flat = inputs(jax.random.PRNGKey(SEED + 3), gidx)
    fn = jax.jit(functools.partial(embedding_bag_fused, interpret=False))
    compiled = fn.lower(arena, flat).compile()
    assert_compiled_kernel(compiled)
    out = jax.block_until_ready(compiled(arena, flat))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(arena, flat))
    smoke_ms = (time.perf_counter() - t0) * 1e3

    @jax.jit
    def chunk_err(arena, flat, out, start):
        idx = jax.lax.dynamic_slice_in_dim(flat, start, B)
        got = jax.lax.dynamic_slice_in_dim(out, start, B)
        ref = embedding_bag_ref(arena, idx)
        return jnp.max(jnp.abs(got - ref)), jnp.max(jnp.abs(ref))

    errs = [jax.device_get(chunk_err(arena, flat, out, c))
            for c in range(0, B * k, B)]
    err = max(float(e) for e, _ in errs)
    scale = max(float(m) for _, m in errs)
    ok = err <= 1e-6 + 1e-5 * scale
    say("3 kernel", f"{'ok' if ok else 'FAIL'}: embedding_bag_fused compiled"
        f" (tpu_custom_call present) on shard {s}: arena ({rows}, {dim}) "
        f"bf16 = {arena.nbytes / 1e9} GB, {B * k} bags x {flat.shape[1]} "
        f"slots; max|kernel - embedding_bag_ref| = {err} (max|ref| = "
        f"{scale}); smoke time {smoke_ms} ms (one call, not a benchmark)")
    assert ok, "kernel disagrees with embedding_bag_ref"
    del arena, flat, out


# ---- phase 4 ------------------------------------------------------------


def build_model(plan, raw):
    import jax.numpy as jnp
    from repro.configs.dlrm import FULL
    from repro.models.dlrm import DLRM
    from repro.optim import adam, rowwise_adagrad
    cfg = dataclasses.replace(FULL, embed_dim=plan.dim,
                              n_tables=raw.shape[0])
    return (DLRM(cfg, plan, dtype=jnp.bfloat16), rowwise_adagrad(0.05),
            adam(1e-3))


def init_state(model, emb_opt, dense_opt, out_shardings=None):
    """Params and optimiser states, made on the device (jitted, so the
    random arenas never exist in f32)."""
    import jax
    from repro.models.dlrm import DENSE_PARAMS

    def init(key):
        params = model.init_params(key)
        return (params, emb_opt.init({"arenas": params["arenas"]}),
                dense_opt.init({k: params[k] for k in DENSE_PARAMS}))

    return jax.jit(init, out_shardings=out_shardings)(
        jax.random.PRNGKey(SEED + 4))


def pooled_reference(arenas, plan, gidx) -> np.ndarray:
    """Plain NumPy float32 gather-sum: (B, S*K, P) ids -> (B, S*K, D)."""
    shard = np.repeat(np.arange(plan.n_shards), plan.k_max)
    base = plan.base_rows.reshape(-1)
    rows = arenas[shard[None, :, None],
                  np.where(gidx >= 0, gidx + base[None, :, None], 0)]
    rows = np.where((gidx >= 0)[..., None], rows.astype(np.float32), 0.0)
    return rows.sum(axis=2, dtype=np.float32)


def check_pooled(lookup, arenas, plan, gidx, n_rows: int = 256) -> float:
    """Max relative error of ``lookup`` vs ``pooled_reference`` over the
    first ``n_rows`` batch rows; asserts it is within bf16 resolution."""
    import jax
    import jax.numpy as jnp
    got = np.asarray(jax.jit(lookup)(arenas, None, gidx[:n_rows]))
    ref = pooled_reference(np.asarray(arenas), plan,
                           np.asarray(gidx[:n_rows]))
    err = float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))
    assert err <= float(jnp.finfo(jnp.bfloat16).eps), \
        f"pooled lookups disagree with NumPy: rel err {err}"
    return err


def step_phase(plan, raw, batch, n_steps: int = 5):
    import jax
    from repro.embedding import sharded as E
    from repro.models.dlrm import make_train_step
    model, emb_opt, dense_opt = build_model(plan, raw)
    params, emb_state, dense_state = init_state(model, emb_opt, dense_opt)

    def lookup(a, b, i):
        return E.lookup_unsharded(a, plan.base_rows, i, plan)

    step = jax.jit(make_train_step(model, lookup, emb_opt, dense_opt),
                   donate_argnums=(0, 1, 2))
    t0 = time.perf_counter()
    compiled = step.lower(params, emb_state, dense_state, batch).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    losses, times = [], []
    for _ in range(n_steps):
        t0 = time.perf_counter()
        params, emb_state, dense_state, loss = compiled(
            params, emb_state, dense_state, batch)
        losses.append(float(loss))
        times.append((time.perf_counter() - t0) * 1e3)
    assert np.isfinite(losses).all(), losses
    err = check_pooled(lookup, params["arenas"], plan, batch["gidx"])
    stats = jax.devices()[0].memory_stats() or {}
    rows = shard_rows(plan)
    say("4 step", f"ok: {n_steps} steps of DLRM-{raw.shape[0]} (full hash "
        f"sizes, {int(plan.table_rows.sum())} rows, shard rows "
        f"{rows.tolist()}), batch {batch['gidx'].shape[0]}, arenas "
        f"{params['arenas'].shape} bf16 = {params['arenas'].nbytes / 1e9} GB;"
        f" losses={losses}; pooled lookups of 256 rows vs NumPy f32 rel err "
        f"{err}; compile_s={compile_s} (args {mem.argument_size_in_bytes}, "
        f"temp {mem.temp_size_in_bytes} bytes); process "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} of "
        f"bytes_limit={stats.get('bytes_limit')}; step times {times} ms, "
        f"warm median {float(np.median(times[1:]))} ms (smoke times, not a "
        f"benchmark)")


# ---- four chips -----------------------------------------------------------


def four_chip_phase(plan, raw, batch, devices, n_steps: int = 3):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.embedding import sharded as E
    from repro.models.dlrm import make_train_step

    mesh = jax.make_mesh((1, N_SHARDS), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=devices[:N_SHARDS])
    model, emb_opt, dense_opt = build_model(plan, raw)
    specs = sharded_specs(model, emb_opt, dense_opt)

    def ns(tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                            is_leaf=lambda s: isinstance(s, P))

    with jax.set_mesh(mesh):
        params, emb_state, dense_state = init_state(
            model, emb_opt, dense_opt, out_shardings=ns(specs[:3]))
        batch = jax.device_put(batch, ns(specs[3]))
        shards = sorted((str(s.device), s.index[0].start)
                        for s in params["arenas"].addressable_shards)
        devs = {d for d, _ in shards}
        assert len(devs) == N_SHARDS, shards

        lookup = E.make_sharded_lookup(mesh, plan)
        bases = jnp.asarray(plan.base_rows)
        got = jax.jit(lookup)(params["arenas"], bases, batch["gidx"])
        ref = jax.jit(lambda a, i: E.lookup_unsharded(
            a, plan.base_rows, i, plan))(params["arenas"], batch["gidx"])
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err <= 1e-6, f"sharded lookup != lookup_unsharded: {err}"

        step = jax.jit(make_train_step(model, lookup, emb_opt, dense_opt),
                       in_shardings=ns(specs),
                       out_shardings=(*ns(specs[:3]),
                                      NamedSharding(mesh, P())),
                       donate_argnums=(0, 1, 2))
        losses, times = [], []
        for _ in range(n_steps):
            t0 = time.perf_counter()
            params, emb_state, dense_state, loss = step(
                params, emb_state, dense_state, batch)
            losses.append(float(loss))
            times.append((time.perf_counter() - t0) * 1e3)
        assert np.isfinite(losses).all(), losses
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:N_SHARDS]]
    say("5 four-chip", f"ok: arena shards on {sorted(devs)} "
        f"(row blocks {[i for _, i in shards]}); make_sharded_lookup vs "
        f"lookup_unsharded max|diff| = {err}; {n_steps} sharded steps, "
        f"losses={losses}, step times {times} ms (smoke times); "
        f"peak_bytes_in_use per chip {peaks}")
    report_comm(devices[:N_SHARDS])


def sharded_specs(model, emb_opt, dense_opt):
    """PartitionSpecs of (params, emb_state, dense_state, batch) for the
    (data, model) mesh: arenas and their row-wise accumulators split over
    ``model``, dense nets replicated, the batch split over both axes
    except the indices, which every shard reads whole."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.models.dlrm import DENSE_PARAMS
    from repro.optim.optimizers import OptState
    aparams = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    a_dense = jax.eval_shape(dense_opt.init,
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


# ---- main -----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on 4 chips")
    args = ap.parse_args(argv)

    devs = check_device(N_SHARDS if args.four_chips else 1)
    import jax
    from repro.configs.dlrm import TRAIN_BATCH
    from repro.data.synthetic import make_dlrm_pool

    pool = make_dlrm_pool(seed=SEED)
    agent = train_agent(pool)
    raw, plan = placed_plan(agent, pool)
    batch = make_batch(jax.random.PRNGKey(SEED + 2), plan, raw, TRAIN_BATCH)
    if args.four_chips:
        four_chip_phase(plan, raw, batch, devs)
    else:
        report_comm(devs[:1])
        serve(agent, pool)
        kernel_phase(plan, batch["gidx"])
        step_phase(plan, raw, batch)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
