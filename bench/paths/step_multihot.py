"""The ``step_multihot`` path: the placed DLRM-DCNv2 train step on
multi-hot batches, as users train with it.

The configuration names the tables (rows, fixed multi-hot sizes), the
DCN-v2 widths and the optimizers (``bench/configs/dlrm_dcnv2.json``).
The program runs its normal path: the size-greedy placement
(``core.baselines.expert_place``), ``build_plan`` with the tables' bag
widths, ``DLRM`` with the ``dcn`` interaction, ``make_train_step`` with
the row-wise Adagrad that updates the looked-up rows in place
(``optim.RowWiseAdagrad``), jitted with donated state.

Set-up, the window, the traced steps and the correctness check are
``bench/paths/step.py``'s (``StepCell``'s window, trace and ring, its
``memory_line``, ``bench.check``, ``bench.trace_reduce``); what differs
is the column layout of the batches ``(B, W)``, the weights of the
cross layers, the plain reference (``bench.reference_dcnv2``), the
required work (``bench.work_dcnv2``) and the device time per program
scope (``bench.trace_scopes``) that the per-layer metrics read.  The
cell exposes what ``bench/readings.py`` and ``bench/faults.py`` drive.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time

import numpy as np

from bench import check, work_dcnv2
from bench.layout import make_layout
from bench.paths.step import StepCell, memory_line
from bench.peaks import peaks
from bench.program import Program
from bench.reference import Readings
from bench.reference_dcnv2 import (Columns, Reference, dense_leaves,
                                   dense_weights, leaf_names, mlp_sizes,
                                   weight_keys)
from bench.trace_reduce import hlo_op_names, load, reduce_trace
from bench.trace_scopes import scope_times
from bench.traffic_gen import key_of
from bench.weights import make_arenas

N_FEATURES = 21
DIM, HASH_SIZE, POOLING, TABLE_SIZE_GB = 0, 1, 2, 3


def table_features(config: dict) -> np.ndarray:
    """(M, 21) raw features of the tables: dim, rows, ids a sample, size
    in GB; the access histogram is left empty (the placement is by size)."""
    rows = np.asarray(config["num_embeddings_per_feature"], np.float64)
    raw = np.zeros((rows.shape[0], N_FEATURES))
    raw[:, DIM] = config["embedding_dim"]
    raw[:, HASH_SIZE] = rows
    raw[:, POOLING] = config["multi_hot_sizes"]
    item = {"bfloat16": 2, "float32": 4}[config["dtype"]]
    raw[:, TABLE_SIZE_GB] = config["embedding_dim"] * rows * item / 1e9
    return raw


def build(raw: np.ndarray, assignment: np.ndarray, config: dict) -> Program:
    """The program's DLRM-DCNv2 on its normal path (fails on a program
    without the ``dcn`` interaction, bag widths or the row update)."""
    import jax.numpy as jnp
    from repro.embedding import sharded as E
    from repro.embedding.plan import build_plan
    from repro.models.dlrm import DLRM, DLRMConfig
    from repro.optim import RowWiseAdagrad, adam

    plan = build_plan(raw, assignment, config["n_shards"],
                      pad_dim_to=config["embedding_dim"],
                      widths=config["multi_hot_sizes"],
                      pad_rows_to=config["pad_rows_to"])
    bottom, top = (config["dense_arch_layer_sizes"],
                   config["over_arch_layer_sizes"])
    model = DLRM(DLRMConfig(n_dense_features=config["num_dense_features"],
                            embed_dim=plan.dim, bottom_mlp=tuple(bottom[:-1]),
                            top_mlp=tuple(top[:-1]),
                            n_tables=raw.shape[0],
                            interaction=config["interaction_type"],
                            cross_layers=config["dcn_num_layers"],
                            cross_rank=config["dcn_low_rank_dim"]),
                 plan, dtype=jnp.dtype(config["dtype"]))
    eo, do = config["emb_optimizer"], config["dense_optimizer"]

    def lookup(arenas, bases, gidx):
        return E.lookup_unsharded(arenas, plan.base_rows, gidx, plan)

    return Program(plan, model,
                   RowWiseAdagrad(eo["lr"], eps=eo["eps"]),
                   adam(do["lr"], b1=do["b1"], b2=do["b2"], eps=do["eps"]),
                   lookup, None)


def columns(layout, widths) -> Columns:
    """Shard 0's columns: each slot's table repeated by its width."""
    tables = layout.slot_table[0][layout.slot_table[0] >= 0]
    reps = np.asarray(widths)[tables]
    return Columns(base=np.repeat(layout.base_rows[0][:tables.size], reps),
                   table=np.repeat(tables, reps),
                   rows=np.repeat(layout.table_rows[tables], reps))


class MultihotCell:
    """DLRM-DCNv2 under one multi-hot traffic mix on one chip."""

    # as the DLRM-50 cells run them
    mesh_context = StepCell.mesh_context
    ring = StepCell.ring
    window = StepCell.window
    traced = StepCell.traced
    _rows = staticmethod(StepCell._rows)

    def __init__(self, config: dict, traffic: dict, devices):
        import jax
        from repro.core.baselines import expert_place
        if config["n_shards"] != 1 or config["lookup"] != "unsharded":
            raise ValueError("the multi-hot path runs one shard on one chip")
        self.config, self.traffic = config, traffic
        self.devices = list(devices)
        raw = table_features(config)
        self.assignment = expert_place(raw, 1, config["capacity_gb"],
                                       config["placement"])
        self.layout = make_layout(self.assignment, raw[:, HASH_SIZE], 1)
        self.prog = build(raw, self.assignment, config)
        plan = self.prog.plan
        if not (np.array_equal(plan.slot_table, self.layout.slot_table)
                and np.array_equal(plan.base_rows, self.layout.base_rows)
                and plan.rows_max >= self.layout.rows_max):
            raise RuntimeError("the program's plan lays the arena out "
                               "otherwise than the placement implies")
        self.rows_max = plan.rows_max
        self.cols = columns(self.layout, config["multi_hot_sizes"])
        self.sizes = mlp_sizes(config)
        self.batch = config["batch_size"]
        one = jax.sharding.SingleDeviceSharding(self.devices[0])
        self.one = one
        state = jax.eval_shape(self._init, weight_keys(0))
        batch = jax.eval_shape(self._make_batch, key_of(0, "batch", 0))
        self.shard = jax.tree.map(lambda a: one, (*state, batch))
        self.make_batch = jax.jit(self._make_batch, out_shardings=one)
        self.init = jax.jit(self._init, out_shardings=one)
        from repro.models.dlrm import make_train_step
        self.step = jax.jit(
            make_train_step(self.prog.model, self.prog.lookup,
                            self.prog.emb_opt, self.prog.dense_opt),
            in_shardings=self.shard, out_shardings=(*self.shard[:3], one),
            donate_argnums=(0, 1, 2))
        self.grad_norms = jax.jit(self._grad_norms)
        self.change = jax.jit(self._change)
        self.rows = jax.jit(self._rows)
        self.compiled = None

    # ---- state and batches ------------------------------------------------

    def _make_batch(self, key):
        import jax
        import jax.numpy as jnp
        k1, k2, k3 = jax.random.split(key, 3)
        rows = jnp.asarray(self.cols.rows, jnp.float32)[None, :]
        u = jax.random.uniform(k1, (self.batch, rows.shape[1]))
        ids = jnp.minimum(jnp.floor(u * rows), rows - 1).astype(jnp.int32)
        return {"dense": jax.random.normal(
                    k2, (self.batch, self.config["num_dense_features"]),
                    jnp.float32),
                "gidx": ids,
                "labels": jax.random.bernoulli(
                    k3, self.traffic["label_rate"], (self.batch,))
                .astype(jnp.float32)}

    def _init(self, keys):
        import jax.numpy as jnp
        c = self.config
        dtype = jnp.dtype(c["dtype"])
        params = {"arenas": make_arenas(keys["arenas"], 1, self.rows_max,
                                        c["embedding_dim"], dtype),
                  **dense_weights(keys, c, dtype)}
        dense = {k: params[k] for k in self.prog.model.cfg.dense_keys}
        return (params, self.prog.emb_opt.init({"arenas": params["arenas"]}),
                self.prog.dense_opt.init(dense))

    def start(self, seed: int):
        """The seed's initial state; compiles the step the first time."""
        import jax
        state = self.init(weight_keys(seed))
        if self.compiled is None:
            want = jax.eval_shape(self.prog.model.init_params,
                                  jax.random.PRNGKey(0))
            got = jax.eval_shape(lambda: state[0])
            if jax.tree.map(lambda a: (a.shape, a.dtype), want) != \
                    jax.tree.map(lambda a: (a.shape, a.dtype), got):
                raise RuntimeError("benchmark weights do not match the "
                                   "program's parameter layout")
            self.compiled = self.compile(self.step)
        return state

    def compile(self, step):
        """``step`` (jitted) compiled for this cell's state and batch."""
        import jax
        shapes = jax.eval_shape(self._init, weight_keys(0))
        batch = jax.eval_shape(self._make_batch, key_of(0, "batch", 0))
        return step.lower(*jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=self.one),
            (*shapes, batch))).compile()

    # ---- readings of the checked steps -----------------------------------

    def _grad_norms(self, emb_state, dense_state):
        import jax.numpy as jnp
        acc = emb_state.inner["arenas"]
        b1 = self.config["dense_optimizer"]["b1"]
        m = dense_state.inner[0]
        return (jnp.sqrt(self.config["embedding_dim"] * jnp.sum(acc)),
                [jnp.linalg.norm(x.astype(jnp.float32)) / (1 - b1)
                 for x in dense_leaves(m)])

    def _change(self, params, keys):
        import jax.numpy as jnp
        c = self.config
        dtype = jnp.dtype(c["dtype"])
        a0 = make_arenas(keys["arenas"], 1, self.rows_max, c["embedding_dim"],
                         dtype)
        d0 = dense_weights(keys, c, dtype)
        arena = jnp.linalg.norm((params["arenas"].astype(jnp.float32)
                                 - a0.astype(jnp.float32)).reshape(-1))
        dense = [jnp.linalg.norm(x.astype(jnp.float32) - y.astype(
            jnp.float32)) for x, y in zip(dense_leaves(params),
                                          dense_leaves(d0))]
        return arena, dense

    def sample_rows(self, seed: int, gidx) -> tuple:
        """(shard ids, arena rows, table ids) behind ``check.N_SAMPLE``
        lookups of the (B, W) batch ``gidx``, drawn from the seed."""
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17])
        b = rng.integers(0, gidx.shape[0], check.N_SAMPLE)
        c = rng.integers(0, gidx.shape[1], check.N_SAMPLE)
        idx = np.asarray(gidx[b, c])
        return (np.zeros(check.N_SAMPLE, np.int32),
                (self.cols.base[c] + idx).astype(np.int32),
                self.cols.table[c])

    def checked_steps(self, seed: int, state):
        """The first ``checked_steps`` steps, each on a fresh batch; as
        ``StepCell.checked_steps``."""
        import jax
        n = self.traffic["checked_steps"]
        params, es, ds = state
        losses = []
        for t in range(n):
            batch = self.make_batch(key_of(seed, "batch", t))
            if t == 0:
                sample = self.sample_rows(seed, batch["gidx"])
                rows0, _ = jax.device_get(self.rows(params, es, *sample[:2]))
            params, es, ds, loss = self.compiled(params, es, ds, batch)
            del batch
            losses.append(loss)
            if t == 0:
                arena_g, dense_g = jax.device_get(self.grad_norms(es, ds))
                rows, acc = jax.device_get(self.rows(params, es,
                                                     *sample[:2]))
        grad = {"arenas": float(arena_g),
                **dict(zip(leaf_names(params), map(float, dense_g)))}
        arena_c, dense_c = jax.device_get(self.change(params,
                                                      weight_keys(seed)))
        change = {"arenas": float(arena_c),
                  **dict(zip(leaf_names(params), map(float, dense_c)))}
        got = Readings([float(x) for x in losses], grad, change,
                       np.asarray(rows), np.asarray(acc))
        return (params, es, ds), got, np.asarray(rows0), sample

    def reference(self, quant: str | None = None) -> Reference:
        return Reference(self.config, self.cols, self.rows_max,
                         self.make_batch, self.devices[0], quant=quant)


make_cell = MultihotCell


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        limits: dict) -> dict:
    """One run of the cell; returns what ``bench/run.py`` prints."""
    L = cell.layout
    info = [f"placement: {cell.config['placement']}-greedy over 1 shard, "
            f"rows_max={cell.rows_max} tables={int((L.slot_table >= 0).sum())}"
            f" columns={cell.cols.table.size}"]
    state = cell.start(seed)
    state, got, rows0, sample = cell.checked_steps(seed, state)
    ring = cell.ring(seed)
    info.append(memory_line(cell.compiled))
    setup_s = time.perf_counter() - t_start
    out = {"breakdown": None, "layer_ctx": None}
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            state, n, elapsed, losses = cell.traced(state, ring, tmp)
            out.update(_reduce(cell, ring, tmp, n, elapsed))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    else:
        state, n, elapsed, losses = cell.window(state, ring, seconds)
        out["e2e"] = {"step_ms": elapsed / n * 1e3, "setup_s": setup_s}
    dev = cell.devices[0]
    out["memory_peak_bytes"] = (dev.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)
    del state, ring
    ref = cell.reference().run(seed, cell.sizes,
                               cell.traffic["checked_steps"], sample)
    numbers = check.compare(got, ref, rows0, sample[2])
    correct, shown = check.judge(numbers, limits)
    out.update(correct=correct, attempted=n,
               failed=int(sum(not np.isfinite(x) for x in losses)),
               checks=shown, info=info,
               device={"platform": dev.platform, "kind": dev.device_kind,
                       "count": 1})
    return out


def _reduce(cell, ring, trace_dir, n, elapsed):
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    profile = load(files[0])
    names = hlo_op_names(cell.compiled.as_text())
    summary = reduce_trace(profile, names, "bench_emb_lookup",
                           "bench_emb_update")
    count = work_dcnv2.device_count_fn(cell.cols.base, cell.rows_max)
    used = [ring[i % len(ring)]["gidx"] for i in range(n)]
    distinct = float(np.mean([int(jax.device_get(count(g))) for g in used]))
    w = work_dcnv2.step_work(cell.config, cell.batch, distinct)
    ctx = {"summary": summary, "scopes": scope_times(profile, names),
           "steps": n, "chips": 1, "peaks": peaks(cell.devices[0].device_kind),
           "work": w, "step_s": elapsed / n}
    return {"layer_ctx": ctx, "busy_s": summary.busy_s(),
            "window_s": summary.window_s,
            "breakdown": {"device_ops": summary.top_ops(),
                          "idle_gaps": summary.top_gaps()}}
