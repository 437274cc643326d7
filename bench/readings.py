"""Readings behind the limits of a step cell's correctness check.

    python3 bench/readings.py --workload dlrm50.step_uniform --seeds 1,2,3 \
        --control-seeds 1,2,3 --fault-seeds 1,2,3 --out readings.jsonl

In one process (the step compiles once), for each seed: the program's
three checked steps and the plain reference's, compared as a run compares
them (``bench.check.compare``); for each control seed, the control (the
reference computed in float8, ``bench.reference``) in the program's
place; for each fault seed, each planted fault (``bench.faults``) that
the cell can have, but the state left unchanged, which reads 1 by the
check's measure and needs no run.  One JSON line per reading, with every
leaf's norms; the sampled rows and accumulators of each reading go to an
``.npz`` beside it, so that the numbers can be recomputed off the chip.

The readings are for setting limits (``bench/limits``): the lower reading
of a number is the largest over the program's seeds, the upper one the
smallest over the control's (and, for training cells, over each fault's).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _ints(text: str) -> list:
    return [int(x) for x in text.split(",") if x]


def _line(kind: str, seed: int, got, ref, rows0, tables) -> dict:
    from bench import check
    return {"kind": kind, "seed": seed,
            "raw": {"rows": got.rows, "acc": got.acc, "ref_rows": ref.rows,
                    "ref_acc": ref.acc, "rows0": rows0, "tables": tables},
            "numbers": check.compare(got, ref, rows0, tables),
            "losses": got.losses, "ref_losses": ref.losses,
            "grad_norms": got.grad_norms, "ref_grad_norms": ref.grad_norms,
            "change_norms": got.change_norms,
            "ref_change_norms": ref.change_norms}


def readings(cell, seeds, control_seeds, fault_seeds, emit) -> None:
    from bench import faults
    compiled = None
    ref = cell.reference()
    control = cell.reference(quant="fp8")
    n = cell.traffic["checked_steps"]
    for seed in seeds:
        t0 = time.perf_counter()
        with cell.mesh_context():
            state = cell.start(seed)
            compiled = compiled or cell.compiled
            cell.compiled = compiled
            state, got, rows0, sample = cell.checked_steps(seed, state)
            del state
        t1 = time.perf_counter()
        want = ref.run(seed, cell.sizes, n, sample)
        t2 = time.perf_counter()
        emit(dict(_line("program", seed, got, want, rows0, sample[2]),
                  program_s=t1 - t0, reference_s=t2 - t1))
        if seed in control_seeds:
            emit(_line("control", seed,
                       control.run(seed, cell.sizes, n, sample), want,
                       rows0, sample[2]))
        if seed in fault_seeds:
            for fault in faults.FAULTS:
                if fault == "unchanged" or not faults.applies(fault, cell):
                    continue
                with cell.mesh_context():
                    cell.compiled = faults.plant(fault, cell)
                    state = cell.start(seed)
                    state, bad, rows0_f, _ = cell.checked_steps(seed, state)
                    del state
                cell.compiled = compiled
                emit(_line(fault, seed, bad, want, rows0_f, sample[2]))


def main(argv=None) -> int:
    from bench.run import (_module, check_device, enable_cache, load_spec,
                           resolve)
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    found = resolve(load_spec(), args.workload)
    devices = check_device(found["cell"]["chips"])
    enable_cache()
    path = _module(found["path"], "bench_path")
    cell = path.make_cell(found["config"], found["traffic"], devices)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        def emit(line):
            np.savez_compressed(
                f"{args.out}.{line['kind']}.{line['seed']}.npz",
                **line.pop("raw"))
            line = dict(line, workload=args.workload)
            f.write(json.dumps(line) + "\n")
            f.flush()
            print(json.dumps({k: line[k] for k in
                              ("kind", "seed", "numbers")}), flush=True)
        readings(cell, args.seeds, set(args.control_seeds),
                 set(args.fault_seeds), emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
