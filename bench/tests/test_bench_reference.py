"""The plain reference agrees with the program's step at a tiny size."""

import json
import os
from unittest import mock

import numpy as np
import pytest

from bench import run

ROOT = run.ROOT


ROW_CAP = 5000


def capped_pool(n_tables, seed):
    """The benchmark's pool with every table cut to ``ROW_CAP`` rows."""
    from bench.pool import HASH_SIZE, make_pool
    raw, zipf = make_pool(n_tables, seed)
    raw[:, HASH_SIZE] = np.minimum(raw[:, HASH_SIZE], ROW_CAP)
    return raw, zipf


def tiny_cell(dtype, n_devices=1):
    import jax
    from bench.paths import step
    with open(os.path.join(ROOT, "bench", "configs", "dlrm50.json")) as f:
        cfg = json.load(f)
    cfg.update(dtype=dtype, n_tables=8, batch=1024)
    if n_devices > 1:
        cfg.update(lookup="sharded")
    with open(os.path.join(ROOT, "bench", "traffic",
                           "train_zipf16.json")) as f:
        trf = json.load(f)
    with mock.patch.object(step, "make_pool", capped_pool):
        return step.make_cell(cfg, trf, jax.devices()[:n_devices])


def readings(cell, seed, ref):
    from bench import check
    with cell.mesh_context():
        state = cell.start(seed)
        state, got, rows0, sample = cell.checked_steps(seed, state)
    want = ref.run(seed, cell.sizes, cell.traffic["checked_steps"], sample)
    return check.compare(got, want, rows0, sample[2])


def test_reference_follows_the_program_step_in_float32():
    cell = tiny_cell("float32")
    numbers = readings(cell, 4, cell.reference())
    assert numbers["loss_gap"] < 1e-5
    assert numbers["grad_gap"] < 1e-5
    assert numbers["change_gap"] < 1e-4
    assert numbers["row_gap"] < 1e-2
    assert numbers["acc_gap"] < 1e-3


@pytest.mark.parametrize("seed", [1, 2**33 + 5])
def test_batches_and_weights_repeat_from_the_seed(seed):
    cell = tiny_cell("bfloat16")
    a = readings(cell, seed, cell.reference())
    b = readings(cell, seed, cell.reference())
    assert a == b
    assert all(np.isfinite(v) for v in a.values())
