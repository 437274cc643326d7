"""The DLRM-DCNv2 cell's own pieces: its configuration, required work,
device time per program scope, metric readers, and its plain reference
against the program at a small size on the CPU."""

import json
import os

import numpy as np
import pytest

from bench import run, trace_reduce as tr, trace_scopes, work_dcnv2
from bench.peaks import peaks
from bench.program import LOOKUP_SCOPE, UPDATE_SCOPE

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = run.load_spec()
CELL = "dlrm_dcnv2.step_multihot"
READERS = ("dcnv2_step_mfu", "dcn_cross_roofline",
           "emb_multihot_fwd_roofline", "emb_row_update_roofline",
           "device_idle.step_multihot")


@pytest.fixture(scope="module")
def config():
    return run.resolve(SPEC, CELL)["config"]


def test_config_keeps_the_published_widths(config):
    """Every width as published; only the five big tables' rows (the
    8-chip row split) and the batch (a chip's share) are cut."""
    assert config["embedding_dim"] == 128
    assert config["multi_hot_sizes"] == [
        3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27,
        10, 3, 1, 1]
    assert sum(config["multi_hot_sizes"]) == 214
    assert config["dense_arch_layer_sizes"] == [512, 256, 128]
    assert config["over_arch_layer_sizes"] == [1024, 1024, 512, 256, 1]
    assert (config["dcn_num_layers"], config["dcn_low_rank_dim"]) == (3, 512)
    pub = config["published_num_embeddings_per_feature"]
    held = config["num_embeddings_per_feature"]
    cut = [i for i, (p, h) in enumerate(zip(pub, held)) if p != h]
    assert cut == [0, 9, 19, 20, 21]
    assert all(pub[i] == 40_000_000 and held[i] == 5_000_000 for i in cut)
    assert config["batch_size"] * 8 == 65536
    (entry,) = [c for c in SPEC["configs"] if c["name"] == "dlrm_dcnv2"]
    assert set(entry["reduced"]) == set(config["reduced"])


def test_required_work_of_the_cell(config):
    """16.03 M MACs a sample (10.6 M of them the cross network), 214 ids
    a sample of 256 bytes."""
    w = work_dcnv2.step_work(config, 8192, 1.39e6)
    macs = w.flops / 6 / 8192
    assert macs == pytest.approx(16.03e6, rel=2e-3)
    assert w.cross_flops / 6 / 8192 == pytest.approx(3 * 2 * 3456 * 512)
    assert w.lookup_bytes == pytest.approx(8192 * 214 * 256
                                           + 8192 * 26 * 128 * 4)
    assert w.update_bytes == pytest.approx(8192 * 26 * 128 * 4
                                           + 1.39e6 * 2 * (256 + 4))


def test_distinct_rows_counted_on_device_and_host():
    rng = np.random.default_rng(0)
    gidx = rng.integers(0, 50, (64, 7)).astype(np.int32)
    base = np.array([1, 1, 51, 51, 51, 101, 151])
    count = work_dcnv2.device_count_fn(base, 200)
    assert int(count(gidx)) == np.unique(gidx + base[None, :]).size


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data",
                           "small_scoped_step.op_names.json")) as f:
        names = json.load(f)
    path = os.path.join(HERE, "data", "small_scoped_step.xplane.pb")
    profile = tr.load(path)
    return (trace_scopes.scope_times(profile, names),
            tr.reduce_trace(profile, names, LOOKUP_SCOPE, UPDATE_SCOPE))


def test_scope_times_of_a_recorded_step(recorded):
    """The small placed step recorded on a v5e: each layer's scope has
    device time, the embedding's forward and backward apart, and no
    scope more than the chip was busy."""
    times, summary = recorded
    busy = summary.chips[0].busy_s
    for scope in ("dlrm.interact", "emb.lookup", "dlrm.bottom",
                  "dlrm.emb_update"):
        assert 0 < trace_scopes.total(times, scope) < busy, scope
    assert trace_scopes.total(times, "emb.lookup", "fwd") > 0
    assert trace_scopes.total(times, "emb.lookup", "bwd") > 0
    assert trace_scopes.total(times, "dlrm.cross.0") == 0
    # the benchmark's lookup scope holds the program's
    assert trace_scopes.total(times, LOOKUP_SCOPE) >= trace_scopes.total(
        times, "emb.lookup")


def _reader(name):
    return run._module(os.path.join(run.ROOT, "bench", "metrics",
                                    f"{name}.py"), name).read


@pytest.mark.parametrize("name", READERS)
def test_readers_read_a_recorded_step(recorded, config, name):
    times, summary = recorded
    if name == "emb_row_update_roofline":       # the recorded step has none
        times = dict(times, **{"emb.update.rows": {"fwd": 1e-4, "bwd": 0}})
    ctx = {"summary": summary, "scopes": times, "steps": 2, "chips": 1,
           "peaks": peaks("TPU v5 lite"), "step_s": summary.window_s / 2,
           "work": work_dcnv2.step_work(config, 8, 1000)}
    assert 0 < _reader(name)(ctx) < 100


@pytest.mark.parametrize("name", READERS[1:4])
def test_readers_find_nothing_without_their_scopes(recorded, config, name):
    """A trace whose ops carry none of a reader's scopes (here the
    recorded step without its names) gives nothing and raises nothing."""
    times, summary = recorded
    ctx = {"summary": summary, "scopes": {}, "steps": 2, "chips": 1,
           "peaks": peaks("TPU v5 lite"), "step_s": summary.window_s / 2,
           "work": work_dcnv2.step_work(config, 8, 1000)}
    assert _reader(name)(ctx) is None
    if name == "emb_row_update_roofline":       # the dot step has no rows
        assert _reader(name)(dict(ctx, scopes=times)) is None


def tiny_cell(dtype: str):
    """The cell at a small size on the CPU: tables of at most 3,000 rows,
    batch 64, low rank 32; every width else as configured."""
    import jax
    found = run.resolve(SPEC, CELL)
    cfg = dict(found["config"], batch_size=64, dcn_low_rank_dim=32,
               dtype=dtype)
    cfg["num_embeddings_per_feature"] = [
        min(r, 3000) for r in cfg["num_embeddings_per_feature"]]
    path = run._module(found["path"], "bench_path_multihot")
    return path.make_cell(cfg, found["traffic"], jax.devices()[:1])


def test_reference_matches_the_program_in_f32():
    """With f32 weights the program's three checked steps and the plain
    reference's agree to f32 rounding (the program's matmuls at HIGHEST
    precision on the CPU), where bf16 weights part them by the limits'
    order."""
    from bench import check
    cell = tiny_cell("float32")
    state = cell.start(11)
    _, got, rows0, sample = cell.checked_steps(11, state)
    ref = cell.reference().run(11, cell.sizes, 3, sample)
    numbers = check.compare(got, ref, rows0, sample[2])
    assert max(numbers.values()) < 2e-3, numbers
    control = cell.reference(quant="fp8").run(11, cell.sizes, 3, sample)
    worse = check.compare(control, ref, rows0, sample[2])
    assert max(worse.values()) > 10 * max(numbers.values()), worse
