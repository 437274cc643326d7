"""Batched DreamShard serving: decode many tasks per jitted call.

``DreamShard.place`` retraces its rollout for every distinct table count
``M`` (and device count ``D``) -- a 50-task suite with heterogeneous sizes
pays tens of XLA compiles.  ``PlacementSession`` instead buckets tasks by
padded ``(M_pad, D)`` shape, pads each task's (sorted) features to the
bucket's table count with masked rows, and decodes the whole bucket in ONE
vmapped+jitted call: one compile per (bucket shape, power-of-two batch
size), amortized across every task in the bucket and every future
``place_many`` call on the session.

The padded rollout is exact, not approximate: masked rows contribute
nothing to the policy/cost device sums or memory, and the candidate key
schedule matches ``DreamShard.place``, so the session returns the *same*
assignments as per-task ``place`` -- just much faster (see
``benchmarks/b4_session_throughput.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry as tele
from repro.api.placement import Placement, measure_placements
from repro.core import features as FEAT
from repro.core import rollout as R
from repro.data.tasks import Task
from repro.embedding.plan import build_plan


def pad_feature_batch(entries, m_pad: int, b_pad: int | None = None):
    """Pad per-task ``(feats (m, F), sizes (m,))`` pairs into one dense
    batch: ``(feats (B, m_pad, F), sizes (B, m_pad), tmask (B, m_pad))``.

    Rows beyond each task's table count (and whole batch rows beyond
    ``len(entries)`` when ``b_pad`` over-allocates to a power of two) are
    zero with ``tmask == 0``.  Shared by ``PlacementSession.place_many``
    and the fused trainer's batched collect / RL task batches, so serving
    and training pad identically.
    """
    B = len(entries) if b_pad is None else b_pad
    feats = np.zeros((B, m_pad, FEAT.NUM_FEATURES), np.float32)
    sizes = np.zeros((B, m_pad), np.float32)
    tmask = np.zeros((B, m_pad), np.float32)
    for j, (f, s) in enumerate(entries):
        m = f.shape[0]
        feats[j, :m] = f
        sizes[j, :m] = s
        tmask[j, :m] = 1.0
    return feats, sizes, tmask


def pad_device_mask(device_counts, d_pad: int) -> np.ndarray:
    """(B, d_pad) mask with row b's first ``device_counts[b]`` entries 1."""
    dmask = np.zeros((len(device_counts), d_pad), np.float32)
    for j, d in enumerate(device_counts):
        dmask[j, :d] = 1.0
    return dmask


class PlacementSession:
    """Long-lived serving handle for one trained DreamShard agent.

    Parameters
    ----------
    agent: a ``DreamShard`` (trained or not; uses its current networks).
    n_candidates: candidate placements ranked per task (default: the
        agent's ``inference_candidates``).
    bucket_tables: bucket granularity -- table counts are padded up to the
        next multiple, trading a little padded compute for far fewer
        compiles across heterogeneous suites.
    refiner: optional post-decode refinement pass -- anything with a
        ``refine(task, placement) -> Placement`` method (canonically a
        ``repro.search.SearchPlacer``).  Each decoded placement is handed
        to the refiner before being returned, so a session can serve
        RL+search placements under one handle; ``refiner=None`` (the
        default) serves the raw decode.
    """

    def __init__(self, agent, n_candidates: int | None = None,
                 bucket_tables: int = 8, refiner=None):
        self.agent = agent
        self._n_candidates_override = n_candidates
        self.bucket_tables = max(1, bucket_tables)
        self.refiner = refiner
        self.num_compiles = 0          # distinct bucket shapes traced
        self.num_decode_calls = 0      # jitted decode invocations
        self._decode_fns: dict[tuple, callable] = {}

    @property
    def n_candidates(self) -> int:
        """Candidates ranked per task -- read live from the agent's config
        (unless overridden) so a config change, e.g. via ``restore``, never
        lets the session drift from per-task ``place``."""
        if self._n_candidates_override is not None:
            return self._n_candidates_override
        return self.agent.cfg.inference_candidates

    # ---- bucketing -----------------------------------------------------------

    def _pad_tables(self, m: int) -> int:
        b = self.bucket_tables
        return int(np.ceil(m / b) * b)

    def bucket_key(self, task: Task) -> tuple[int, int]:
        return (self._pad_tables(task.n_tables), task.n_devices)

    def _decode_fn(self, m_pad: int, n_devices: int, b_pad: int):
        cfg = self.agent.cfg
        # cfg-derived statics are part of the key: a config change on a
        # live agent (e.g. restore()) must not serve stale traces
        key = (m_pad, n_devices, self.n_candidates, b_pad,
               cfg.use_cost_features, cfg.reward_mode, self.agent._log_targets)
        fn = self._decode_fns.get(key)
        if fn is None:
            self.num_compiles += 1
            tele.count("session.bucket_compiles")
            tele.count("jit.retraces")
            decode = functools.partial(
                R.decode_candidates, n_devices=n_devices,
                n_candidates=self.n_candidates,
                use_cost=cfg.use_cost_features, reward_mode=cfg.reward_mode,
                log_targets=self.agent._log_targets)

            @jax.jit
            def fn(policy_params, cost_params, feats, sizes, tmask, cap):
                def one(f, s, m):
                    return decode(policy_params, cost_params, f, s, cap,
                                  tmask=m)
                return jax.vmap(one)(feats, sizes, tmask)

            self._decode_fns[key] = fn
        return fn

    # ---- serving -------------------------------------------------------------

    def place_many(self, tasks: list[Task]) -> list[Placement]:
        """Place a suite, decoding each ``(M_pad, D)`` bucket in one call."""
        tasks = list(tasks)
        buckets: dict[tuple, list[int]] = {}
        for i, t in enumerate(tasks):
            buckets.setdefault(self.bucket_key(t), []).append(i)

        out: list[Placement | None] = [None] * len(tasks)
        for (m_pad, n_devices), idxs in buckets.items():
            B = len(idxs)
            # pad the batch dim to a power of two with fully-masked rows so
            # differently-sized calls into the same bucket reuse one trace
            b_pad = 1 << max(0, B - 1).bit_length()
            entries, orders = [], []
            for i in idxs:
                f, s, order = self.agent._inference_inputs(
                    tasks[i].raw_features)
                entries.append((f[order], s[order]))
                orders.append(order)
            feats, sizes, tmask = pad_feature_batch(entries, m_pad, b_pad)
            c0 = self.num_compiles
            fn = self._decode_fn(m_pad, n_devices, b_pad)
            fresh = self.num_compiles > c0
            args = (self.agent.policy_params, self.agent.cost_params,
                    jnp.asarray(feats), jnp.asarray(sizes),
                    jnp.asarray(tmask), self.agent.oracle.mem_capacity_gb)
            # the span ends when the host holds the result; a fresh fn
            # (``fresh_compile``) pays its jit trace and compile inside it
            with tele.span("session.decode", m_pad=m_pad,
                           n_devices=n_devices, tasks=B, b_pad=b_pad,
                           fresh_compile=fresh):
                actions, est = fn(*args)
                actions, est = np.asarray(actions), np.asarray(est)
            self.num_decode_calls += 1
            tele.count("session.decode_calls")
            for j, i in enumerate(idxs):
                t, order = tasks[i], orders[j]
                best = int(np.argmin(est[j]))
                assignment = np.empty(t.n_tables, dtype=np.int64)
                assignment[order] = actions[j, best, :t.n_tables]
                out[i] = Placement(
                    assignment=assignment,
                    plan=build_plan(t.raw_features, assignment, n_devices),
                    n_devices=n_devices, strategy="dreamshard",
                    est_cost_ms=float(est[j, best]),
                    candidates=self.n_candidates, oracle_evals=0)
        if self.refiner is not None:
            out = [self.refiner.refine(t, p) for t, p in zip(tasks, out)]
        return out

    def place(self, task: Task) -> Placement:
        return self.place_many([task])[0]

    def place_and_measure(self, tasks: list[Task], oracle
                          ) -> tuple[list[Placement], np.ndarray]:
        """Serve a suite end-to-end batched: bucketed decode
        (``place_many``) followed by one grouped ``evaluate_many``
        measurement pass per distinct (raw features, device count) --
        both halves scale with vector width, not task count.  Returns
        ``(placements, per-task measured ms)``."""
        tasks = list(tasks)
        placements = self.place_many(tasks)
        return placements, measure_placements(oracle, tasks, placements)
