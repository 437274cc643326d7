"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per benchmark plus a JSON dump of
all rows.  Quick budgets by default; set REPRO_BENCH_FULL=1 for
paper-scale budgets.

  PYTHONPATH=src python -m benchmarks.run [--only table1_main]
"""

from __future__ import annotations

import argparse
import importlib
import json
import time
import traceback

MODULES = [
    "table1_main",            # Table 1/6/7: cost vs baselines
    "table2_generalization",  # Table 2/8-10: zero-shot transfer
    "table3_ablation",        # Table 3/11: feature + cost ablations
    "fig5_efficiency",        # Fig 5: cost vs iterations / wall time
    "fig7_costnet_data",      # Fig 7: cost-net data scaling
    "fig8_estimated_mdp",     # Fig 8: estimated vs real MDP
    "table4_comm_imbalance",  # Table 4: comm vs imbalance
    "fig12_fusion",           # Fig 12: operation-fusion analysis
    "b3_reductions",          # App B.3: sum/max reduction comparison
    "b4_session_throughput",  # PlacementSession batched serving vs per-task
    "b5_sim2real",            # calibration + MeasuredOracle vs SimOracle
    "b6_train_throughput",    # fused Algorithm-1 loop vs seed per-step loop
    "b7_oracle_throughput",   # batched evaluate_many vs per-placement loop
    "b8_fusion_model",        # fusion-aware vs additive multi-table costs
    "b9_search",              # search-augmented placement anytime curves
    "b10_telemetry_overhead",  # telemetry off-path / enabled overhead bounds
    "b11_serve",              # placement serving: cache, admission, drift
    "b12_resilience",         # fault injection, failover, degraded serving
    "b13_sharding",           # column-wise sharding: feasibility + K=1 identity
    "beyond_paper_ablation",  # DESIGN 4b refinements, each reverted
    "kernel_embedding_bag",   # FBGEMM-analogue kernel timing
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="bench_results.json")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record telemetry and export a trace on exit "
                         "(.jsonl -> event log, else Chrome trace JSON "
                         "for chrome://tracing)")
    args = ap.parse_args()
    mods = [args.only] if args.only else MODULES

    from repro import telemetry as tele
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    all_rows = {}
    with tele.trace_to(args.trace):
        print("name,us_per_call,derived")
        for name in mods:
            t0 = time.perf_counter()
            try:
                with tele.span("bench.module", module=name):
                    mod = importlib.import_module(f"benchmarks.{name}")
                    rows = mod.run()
                status = "ok"
            except Exception as e:
                rows = [{"error": f"{type(e).__name__}: {e}"}]
                traceback.print_exc()
                status = "error"
            dt = time.perf_counter() - t0
            all_rows[name] = {"status": status, "seconds": round(dt, 1),
                              "rows": rows}
            print(f"{name},{dt * 1e6 / max(len(rows), 1):.0f},"
                  f"status={status} rows={len(rows)} wall={dt:.1f}s",
                  flush=True)
    json.dump(all_rows, open(args.out, "w"), indent=1, default=str)
    print(f"results -> {args.out}")


if __name__ == "__main__":
    main()
