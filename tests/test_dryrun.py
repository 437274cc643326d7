"""The DLRM compile rehearsal (``repro.launch.dryrun``) lowers and
compiles the table-parallel step on small meshes of CPU devices, and its
roofline terms find the lookup's all-to-all.  Each mesh runs in its own
process with 8 host devices; importing the module changes no flag."""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys; sys.path.insert(0, sys.argv[1])
import jax
from repro.launch import dryrun
assert os.environ["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"
shape = tuple(int(n) for n in sys.argv[2].split("x"))
mesh = jax.make_mesh(shape, ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rec = dryrun.run_dlrm(mesh, batch=64, n_tables=12)
print("REC", json.dumps(rec))
"""


@pytest.mark.parametrize("shape", ["1x4", "2x4"])
def test_rehearsal_compiles_and_finds_the_all_to_all(shape):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT, src, shape],
                       capture_output=True, text=True, timeout=600)
    lines = [line[4:] for line in r.stdout.splitlines()
             if line.startswith("REC ")]
    assert r.returncode == 0 and lines, r.stdout + r.stderr[-3000:]
    rec = json.loads(lines[0])
    data, model = map(int, shape.split("x"))
    assert rec["mesh_shape"] == {"data": data, "model": model}
    assert rec["n_devices"] == data * model
    wire = rec["roofline"]["wire_by_kind"]
    assert wire["all-to-all"] > 0, wire
    assert rec["roofline"]["hlo_flops_per_dev"] > 0
    assert 0 < rec["arg_bytes_per_dev"] <= rec["peak_bytes_per_dev"]
    assert rec["fits_16gb_hbm"]
