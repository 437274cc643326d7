"""B6: fused Algorithm-1 trainer throughput vs the seed per-step loop.

The seed loop pays per-step host costs everywhere: ``update_cost`` issues
``n_cost`` sequential jit dispatches (each rebuilding + re-uploading a
padded numpy minibatch), ``collect`` decodes one rollout per jit call
(plus an eager per-task sort), and ``update_policy`` dispatches per step
and retraces per ``(n_devices, n_episodes)`` shape.  The fused trainer
(``DreamShardConfig(fused=True)``) runs each stage as ONE dispatch: a
vmapped padded collect whose oracle measurements go through the batched
``evaluate_many`` path (one vectorized pass per distinct task -- see
``benchmarks/b7_oracle_throughput.py`` for the oracle-side numbers), a
donated ``lax.scan`` over the device-resident replay ring, and a scan over
a padded task batch for REINFORCE -- and the two loops are numerically
equivalent (same RNG streams, same updates; see
``tests/test_fused_trainer.py``), so speedup comes with identical final
eval cost.

Two measured regimes on the 20-table/4-device suite:

* ``paper``  -- the paper's Algorithm-1 budget (n_collect=10, n_cost=300,
  n_batch=64, n_rl=10).  On CPU-only hosts the 300x64 minibatch matmuls
  dominate both variants, so this regime mostly bounds the wall win from
  below while showing the dispatch/retrace elimination.
* ``scale``  -- the collection-bound regime the paper's successors hit at
  scale (Pre-train-and-Search: the cost-model data pipeline is the
  bottleneck): 10x the measurements per iteration (n_collect=100) with
  lean minibatches (n_batch=8) that keep a 2-core CI host measuring loop
  overhead rather than matmul throughput.  This is the headline row.

Per-iteration wall-clock is the MEDIAN over warm iterations (>= 1;
iteration 0 carries each variant's compiles, reported separately), since
the per-step loop's hundreds of sync'd dispatches make it noisy on shared
hosts.  Writes ``BENCH_train.json`` (committed at the repo root; CI
uploads a fresh copy per run) so the training-throughput trajectory
accumulates across PRs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import common as C                  # noqa: E402,F401
from repro.core.trainer import DreamShard, DreamShardConfig  # noqa: E402
from repro.data.synthetic import make_dlrm_pool     # noqa: E402
from repro.data.tasks import make_benchmark_suite   # noqa: E402
from repro.sim.costsim import CostSimulator         # noqa: E402

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _regimes(smoke: bool):
    if smoke:
        base = dict(n_iterations=3, n_collect=20, n_cost=40, n_rl=4)
        return {"scale": DreamShardConfig(n_batch=8, **base)}
    base = dict(n_iterations=10, n_collect=10, n_cost=300, n_rl=10)
    return {
        "paper": DreamShardConfig(n_batch=64, **base),
        "scale": DreamShardConfig(n_batch=8, **{**base, "n_collect": 100}),
    }


def _compiles(agent: DreamShard) -> int:
    """Distinct traces the trainer's update functions accumulated."""
    if agent.cfg.fused:
        return (agent._fused_cost_update.traces[0]
                + agent._fused_rl_update.traces[0])
    return len(agent._rl_updates) + agent._cost_update._cache_size()


def _run_variant(fused: bool, cfg: DreamShardConfig, train, test) -> dict:
    sim = CostSimulator(seed=0)
    agent = DreamShard(train, sim, dataclasses.replace(cfg, fused=fused))
    t0 = time.perf_counter()
    agent.train()
    total = time.perf_counter() - t0
    walls = [h["wall_s"] for h in agent.history]
    warm = walls[1:] if len(walls) > 1 else walls
    return {
        "variant": "fused" if fused else "seed",
        "total_wall_s": round(total, 3),
        "iter_wall_s": [round(w, 4) for w in walls],
        "warm_iter_median_s": round(float(np.median(warm)), 4),
        "warm_iter_mean_s": round(float(np.mean(warm)), 4),
        "dispatches_per_iter": agent.history[-1]["dispatches"],
        "compiled_traces": _compiles(agent),
        "final_cost_loss": round(agent.history[-1]["cost_loss"], 6),
        "eval_cost_ms": round(agent.evaluate_tasks(test), 4),
    }


def run(smoke: bool = False, out: str | None = None, repeats: int = 1,
        regimes: list[str] | None = None):
    pool = make_dlrm_pool(seed=0)
    train, test = make_benchmark_suite(pool, n_tables=20, n_devices=4,
                                       n_tasks=10)
    selected = _regimes(smoke)
    if regimes:
        selected = {k: v for k, v in selected.items() if k in regimes}
        if not selected:
            raise SystemExit(f"no such regime(s) {regimes}; "
                             f"have {list(_regimes(smoke))}")
    result = {
        "benchmark": "b6_train_throughput",
        "schema": 1,
        "mode": "smoke" if smoke else "full",
        "generated": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "repeats": repeats,
        "suite": {"n_tables": 20, "n_devices": 4, "n_train_tasks": len(train),
                  "n_eval_tasks": len(test)},
        "host": {"cpu_count": os.cpu_count(),
                 "jax": __import__("jax").__version__},
        "regimes": {},
    }
    for name, cfg in selected.items():
        # alternate seed/fused runs so shared-host load hits both evenly;
        # the per-iteration metric is the median of per-run warm medians
        runs = {"seed": [], "fused": []}
        for rep in range(repeats):
            for fused in (False, True):
                row = _run_variant(fused, cfg, train, test)
                runs[row["variant"]].append(row)
                print({"regime": name, "rep": rep, **row}, flush=True)
        seed_row, fused_row = runs["seed"][-1], runs["fused"][-1]
        seed_med = float(np.median(
            [r["warm_iter_median_s"] for r in runs["seed"]]))
        fused_med = float(np.median(
            [r["warm_iter_median_s"] for r in runs["fused"]]))
        eval_rel = abs(fused_row["eval_cost_ms"] - seed_row["eval_cost_ms"]) \
            / seed_row["eval_cost_ms"]
        summary = {
            "config": {k: getattr(cfg, k) for k in
                       ("n_iterations", "n_collect", "n_cost", "n_batch",
                        "n_rl", "n_episode")},
            "seed": seed_row, "fused": fused_row,
            "seed_warm_iter_medians_s": [r["warm_iter_median_s"]
                                         for r in runs["seed"]],
            "fused_warm_iter_medians_s": [r["warm_iter_median_s"]
                                          for r in runs["fused"]],
            "per_iteration_speedup": round(seed_med / fused_med, 2),
            "total_speedup": round(seed_row["total_wall_s"]
                                   / fused_row["total_wall_s"], 2),
            "dispatch_reduction": round(seed_row["dispatches_per_iter"]
                                        / fused_row["dispatches_per_iter"], 1),
            "eval_rel_diff": round(eval_rel, 5),
        }
        result["regimes"][name] = summary
        print({"regime": name,
               "per_iteration_speedup": summary["per_iteration_speedup"],
               "total_speedup": summary["total_speedup"],
               "dispatch_reduction": summary["dispatch_reduction"],
               "eval_rel_diff": summary["eval_rel_diff"]}, flush=True)

    head_name = "scale" if "scale" in result["regimes"] \
        else next(iter(result["regimes"]))
    head = result["regimes"][head_name]
    result["headline"] = {
        "regime": head_name,
        "per_iteration_speedup": head["per_iteration_speedup"],
        "dispatch_reduction": head["dispatch_reduction"],
        "eval_rel_diff": head["eval_rel_diff"],
    }
    out = out or os.path.join(ROOT, "BENCH_train.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print({"headline": result["headline"], "written": os.path.abspath(out)},
          flush=True)
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny budget for CI: scale regime only")
    ap.add_argument("--out", default=None, help="output JSON path")
    ap.add_argument("--repeats", type=int, default=1,
                    help="alternating seed/fused runs per regime; the "
                         "per-iteration metric is the median across runs")
    ap.add_argument("--regimes", default=None,
                    help="comma-separated regime subset (e.g. 'scale'; CI "
                         "runs the full-config scale regime so the bench "
                         "gate can compare against the committed baseline)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record telemetry and export a trace on exit "
                         "(.jsonl -> event log, else Chrome trace JSON)")
    args = ap.parse_args()
    from repro import telemetry as tele
    with tele.trace_to(args.trace):
        run(smoke=args.smoke, out=args.out, repeats=max(1, args.repeats),
            regimes=args.regimes.split(",") if args.regimes else None)
