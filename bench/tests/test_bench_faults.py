"""The check catches the control and every planted fault of a one-chip
cell: a run with the timed path broken underneath comes out not correct.

The runs skip the harness's look for a chip and drive the rest of a run
(``bench.paths.step.run``) on the CPU, at a size a test can hold, with
the cell's own limits."""

import json
import os
import time

import pytest

from bench import faults, run
from bench.tests.test_bench_reference import tiny_cell

WORKLOAD = "dlrm50.step_uniform"


def limits(workload=WORKLOAD):
    with open(os.path.join(run.ROOT, "bench", "limits",
                           f"{workload}.json")) as f:
        return json.load(f)["limits"]


def run_cell(cell, seed):
    from bench.paths import step
    return step.run(cell, seed, 0.0, False, time.perf_counter(), limits())


@pytest.fixture(scope="module")
def cell():
    return tiny_cell("bfloat16")


@pytest.mark.parametrize("fault", [f for f in faults.FAULTS
                                   if f != "no_exchange"])
def test_a_planted_fault_comes_out_not_correct(cell, fault):
    sound = cell.compiled
    cell.compiled = faults.plant(fault, cell)
    try:
        out = run_cell(cell, 7)
    finally:
        cell.compiled = sound
    assert out["correct"] is False, out["checks"]


def test_the_control_comes_out_not_correct(cell):
    from bench import check
    seed = 7
    with cell.mesh_context():
        state = cell.start(seed)
        state, _, rows0, sample = cell.checked_steps(seed, state)
    n = cell.traffic["checked_steps"]
    ref = cell.reference().run(seed, cell.sizes, n, sample)
    ctl = cell.reference(quant="fp8").run(seed, cell.sizes, n, sample)
    correct, shown = check.judge(check.compare(ctl, ref, rows0, sample[2]),
                                 limits())
    assert correct is False, shown
