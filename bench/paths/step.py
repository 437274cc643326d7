"""The ``step`` path: the placed DLRM train step, as users train with it.

Set-up (all counted in ``setup_s``): the pool, the size-greedy placement
and the plan; the weights and a ring of batches, made on the device from
the seed; the step, compiled once (or found in the compile cache); and
the first three steps of the run, through the compiled step and the ring,
read for the correctness check (``bench.check``).

The window then runs that same compiled step on that same state, one step
queued behind the one running, until a step completes ``seconds`` after
the window opened; the steps still queued are waited for and counted.
``step_ms`` is the window's wall time over the steps it completed.  The
traced run (``--trace 1``) instead profiles two whole steps and reduces
the trace (``bench.trace_reduce``).

After the window the program's state is freed and the plain reference
runs the three checked steps (``bench.reference``).
"""

from __future__ import annotations

import contextlib
import os
import shutil
import tempfile
import time

import numpy as np

from bench import check, program, work
from bench.layout import make_layout, matches_plan
from bench.peaks import peaks
from bench.pool import HASH_SIZE, make_pool
from bench.reference import Readings, Reference
from bench.trace_reduce import hlo_op_names, load, reduce_trace
from bench.traffic_gen import key_of, make_batch_fn, slot_params
from bench.weights import (MLPS, dense_leaves, dense_params, leaf_names,
                           make_arenas, mlp_sizes, weight_keys)

class StepCell:
    """One configuration under one step traffic mix on ``devices``."""

    def __init__(self, config: dict, traffic: dict, devices):
        import jax
        self.config, self.traffic = config, traffic
        self.devices = list(devices)
        pool, zipf = make_pool(config["pool"]["n_tables"],
                               config["pool"]["seed"])
        n = config["n_tables"]
        self.raw, self.zipf = pool[:n], zipf[:n]
        self.assignment = program.place(self.raw, config)
        self.layout = make_layout(self.assignment, self.raw[:, HASH_SIZE],
                                  config["n_shards"])
        self.prog = program.build(self.raw, self.assignment, config,
                                  self.devices)
        if not matches_plan(self.layout, self.prog.plan):
            raise RuntimeError("the program's plan lays the arenas out "
                               "otherwise than the placement implies")
        self.sizes = mlp_sizes(config)
        self.shard = program.shardings(self.prog, self.devices[0])
        self.slots = slot_params(self.layout, self.raw, self.zipf,
                                 config["max_pooling"], traffic["index_law"])
        self.batch = config["batch"]
        self.make_batch = self._batch_fn(self.shard[3])
        self.init = jax.jit(self._init, out_shardings=self.shard[:3])
        self.step = program.make_step(self.prog, self.shard)
        self.grad_norms = jax.jit(self._grad_norms)
        self.change = jax.jit(self._change)
        self.rows = jax.jit(self._rows)
        self.compiled = None

    def mesh_context(self):
        import jax
        if self.prog.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.prog.mesh)

    def _batch_fn(self, out_shardings):
        c = self.config
        return make_batch_fn(self.slots, self.batch, c["n_dense_features"],
                             c["max_pooling"], self.traffic["label_rate"],
                             out_shardings=out_shardings)

    # ---- state -----------------------------------------------------------

    def _init(self, keys):
        import jax.numpy as jnp
        c, L = self.config, self.layout
        dtype = jnp.dtype(c["dtype"])
        params = {"arenas": make_arenas(keys["arenas"], L.n_shards,
                                        L.rows_max, c["embed_dim"], dtype,
                                        mesh=self.prog.mesh),
                  **dense_params(keys, self.sizes, dtype)}
        return (params, self.prog.emb_opt.init({"arenas": params["arenas"]}),
                self.prog.dense_opt.init({k: params[k] for k in MLPS}))

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

        def shaped(tree, shard):
            return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=s), tree, shard)

        key = key_of(0, "batch", 0)
        state = jax.eval_shape(self._init, weight_keys(0))
        return step.lower(*shaped(state, self.shard[:3]),
                          shaped(jax.eval_shape(self.make_batch, key),
                                 self.shard[3])).compile()

    def ring(self, seed: int) -> list:
        """The window's batches: those after the checked steps' ones."""
        first = self.traffic["checked_steps"]
        return [self.make_batch(key_of(seed, "batch", first + i))
                for i in range(self.traffic["ring"])]

    # ---- readings of the checked steps -----------------------------------

    def _grad_norms(self, emb_state, dense_state):
        import jax.numpy as jnp
        acc = emb_state.inner["arenas"].astype(jnp.float32)
        b1 = self.config["dense_optimizer"]["b1"]
        m = dense_state.inner[0]
        return (jnp.sqrt(self.config["embed_dim"] * jnp.sum(acc)),
                [jnp.linalg.norm(x.astype(jnp.float32)) / (1 - b1)
                 for x in dense_leaves(m)])

    def _change(self, params, keys):
        import jax.numpy as jnp
        c, L = self.config, self.layout
        dtype = jnp.dtype(c["dtype"])
        a0 = make_arenas(keys["arenas"], L.n_shards, L.rows_max,
                         c["embed_dim"], dtype, mesh=self.prog.mesh)
        d0 = dense_params(keys, self.sizes, dtype)
        arena = jnp.linalg.norm((params["arenas"].astype(jnp.float32)
                                 - a0.astype(jnp.float32)).reshape(-1))
        dense = [jnp.linalg.norm(x.astype(jnp.float32) - y.astype(
            jnp.float32)) for x, y in zip(dense_leaves(params),
                                          dense_leaves(d0))]
        return arena, dense

    @staticmethod
    def _rows(params, emb_state, shard_ids, rows):
        import jax.numpy as jnp
        return (params["arenas"][shard_ids, rows].astype(jnp.float32),
                emb_state.inner["arenas"][shard_ids, rows]
                .astype(jnp.float32))

    def checked_steps(self, seed: int, state):
        """Runs the first ``checked_steps`` steps, each on a fresh batch;
        returns the state after them, the program's readings, the sampled
        rows' initial values and the sample itself."""
        import jax
        n = self.traffic["checked_steps"]
        params, es, ds = state
        losses = []
        for t in range(n):
            batch = self.make_batch(key_of(seed, "batch", t))
            if t == 0:
                sample = check.sample_rows(seed, self.layout,
                                           self.slots["pool"], batch["gidx"])
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

    # ---- the window --------------------------------------------------------

    def window(self, state, ring, seconds: float, clock=time.perf_counter):
        """Timed steps; returns (state, steps, seconds, losses)."""
        import jax
        cur = {"state": state}
        losses = []

        def dispatch(i):
            with jax.profiler.TraceAnnotation("bench_batch"):
                batch = ring[i % len(ring)]
            with jax.profiler.TraceAnnotation("bench_dispatch"):
                *cur["state"], loss = self.compiled(*cur["state"], batch)
            losses.append(loss)
            return loss

        def wait(loss):
            with jax.profiler.TraceAnnotation("bench_block"):
                loss.block_until_ready()

        n, elapsed = timed_window(dispatch, wait, seconds, clock)
        return tuple(cur["state"]), n, elapsed, [float(x) for x in losses]

    def traced(self, state, ring, trace_dir: str):
        """Profiles two whole steps (a window of 0 seconds) into
        ``trace_dir``; returns what ``window`` does."""
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            return self.window(state, ring, 0.0)
        finally:
            jax.profiler.stop_trace()

    def reference(self, quant: str | None = None) -> Reference:
        import jax
        dev0 = jax.sharding.SingleDeviceSharding(self.devices[0])
        n_dev = len(self.devices) if self.prog.mesh is not None else 1
        return Reference(self.config, self.layout, self._batch_fn(dev0),
                         self.devices[:n_dev], quant=quant)


make_cell = StepCell


def timed_window(dispatch, wait, seconds: float, clock):
    """Dispatches steps back to back, each queued behind the one before,
    until a step completes ``seconds`` or more after the start; waits for
    the one still queued.  Returns (whole steps, wall seconds)."""
    t0 = clock()
    prev = dispatch(0)
    n = 1
    while True:
        cur = dispatch(n)
        n += 1
        wait(prev)
        if clock() - t0 >= seconds:
            break
        prev = cur
    wait(cur)
    return n, clock() - t0


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        limits: dict) -> dict:
    """One run of a step cell; returns what ``bench/run.py`` prints."""
    info = [placement_line(cell)]
    with cell.mesh_context():
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
        used = cell.devices[:len(cell.devices) if cell.prog.mesh else 1]
        out["memory_peak_bytes"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in used)
        del state, ring
    ref = cell.reference().run(seed, cell.sizes,
                               cell.traffic["checked_steps"], sample)
    numbers = check.compare(got, ref, rows0, sample[2])
    correct, shown = check.judge(numbers, limits)
    out.update(correct=correct, attempted=n,
               failed=int(sum(not np.isfinite(x) for x in losses)),
               checks=shown, info=info,
               device={"platform": used[0].platform,
                       "kind": used[0].device_kind, "count": len(used)})
    return out


def _reduce(cell, ring, trace_dir, n, elapsed):
    import glob
    import jax
    import jax.numpy as jnp
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    summary = reduce_trace(load(files[0]),
                           hlo_op_names(cell.compiled.as_text()),
                           program.LOOKUP_SCOPE, program.UPDATE_SCOPE)
    c, L = cell.config, cell.layout
    count = work.device_count_fn(L.k_max, L.base_rows, L.rows_max)
    used = [ring[i % len(ring)]["gidx"] for i in range(n)]
    counts = [tuple(map(int, jax.device_get(count(g)))) for g in used]
    dtype_bytes = jnp.dtype(c["dtype"]).itemsize
    w = [work.step_work(cell.batch, c["n_tables"], c["embed_dim"],
                        cell.sizes, live, distinct, dtype_bytes,
                        dtype_bytes, 4) for live, distinct in counts]
    mean = work.Work(*(float(np.mean([getattr(x, f) for x in w]))
                       for f in ("flops", "emb_fwd_bytes", "emb_bwd_bytes",
                                 "step_bytes")))
    chips = len(summary.chips)
    ctx = {"summary": summary, "steps": n, "chips": chips,
           "peaks": peaks(cell.devices[0].device_kind), "work": mean,
           "step_s": elapsed / n}
    return {"layer_ctx": ctx, "busy_s": summary.busy_s(),
            "window_s": summary.window_s,
            "breakdown": {"device_ops": summary.top_ops(),
                          "idle_gaps": summary.top_gaps()}}


def placement_line(cell) -> str:
    L = cell.layout
    return (f"placement: {cell.config['placement']}-greedy over "
            f"{L.n_shards} shards, rows_max={L.rows_max} k_max={L.k_max} "
            f"shard_rows={L.shard_rows().tolist()} tables_per_shard="
            f"{(L.slot_table >= 0).sum(axis=1).tolist()}")


def memory_line(compiled) -> str:
    m = compiled.memory_analysis()
    return ("step memory per chip (compiled.memory_analysis): "
            f"argument={m.argument_size_in_bytes} "
            f"output={m.output_size_in_bytes} "
            f"alias={m.alias_size_in_bytes} temp={m.temp_size_in_bytes} "
            f"generated_code={m.generated_code_size_in_bytes} bytes")
