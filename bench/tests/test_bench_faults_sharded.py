"""The check catches a four-chip cell's faults, the all-to-all left out
among them, on four virtual CPU devices in a child process."""

import json
import os
import subprocess
import sys

from bench import run

CHILD = r"""
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
from bench import faults
from bench.paths import step
from bench.tests.test_bench_reference import tiny_cell
with open({limits!r}) as f:
    limits = json.load(f)["limits"]
cell = tiny_cell("bfloat16", n_devices=4)
out = {{}}
for fault in faults.FAULTS:
    cell.compiled = faults.plant(fault, cell)
    res = step.run(cell, 3, 0.0, False, time.perf_counter(), limits)
    out[fault] = res["correct"]
print(json.dumps(out))
"""


def test_every_fault_of_a_sharded_cell_comes_out_not_correct():
    limits = os.path.join(run.ROOT, "bench", "limits",
                          "dlrm50.step_uniform.json")
    code = CHILD.format(root=run.ROOT, src=os.path.join(run.ROOT, "src"),
                        limits=limits)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {f: False for f in got} and "no_exchange" in got
