"""Measured-cost profiling subsystem: calibration-table round-trip,
interpolation semantics, the fused multi-table model (v2), MeasuredOracle
protocol/monotonicity, comm model fitting, the calibrate CLI, the
KernelOracle adapter regression, and DreamShard end-to-end on a
MeasuredOracle."""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import CostOracle, KernelOracle, MeasuredOracle
from repro.core.trainer import DreamShard, DreamShardConfig
from repro.data.tasks import sample_tasks, split_pool
from repro.profiling import (CALIBRATION_VERSION, CalibrationTable,
                             CommModel, FusionModel, default_artifact_path,
                             fit_alpha_beta, load_or_none, synthetic_trace)
from repro.profiling.calibrate import main as calibrate_main
from repro.sim.hardware import PAPER_GPU


@pytest.fixture(scope="module")
def synth_table():
    """Deterministic analytic table (no kernels timed, no flakiness)."""
    return CalibrationTable.synthetic(
        dims=(16, 64, 256), rows=(256, 4096), batches=(32, 1024),
        poolings=(2, 8))


@pytest.fixture(scope="module")
def measured_table():
    """A real (tiny) measured table; reuses the CI-cached artifact from
    ``repro.profiling.calibrate --smoke`` when present so the sim-to-real
    tests don't re-measure."""
    cached = load_or_none(default_artifact_path())
    if cached is not None and cached.version == CALIBRATION_VERSION:
        return cached
    return CalibrationTable.measure(
        dims=(16, 64), rows=(128, 1024), batches=(8,), poolings=(2,),
        use_pallas=False, warmup=1, repeats=1)


@pytest.fixture(scope="module")
def tasks20(dlrm_pool):
    _, test_ids = split_pool(dlrm_pool, seed=0)
    return sample_tasks(dlrm_pool, test_ids, 20, 4, 3, seed=5, name="prof")


# ---- calibration table -------------------------------------------------------


def test_table_roundtrip_identical_interpolation(synth_table, tmp_path):
    path = synth_table.save(str(tmp_path / "cal.npz"))
    loaded = CalibrationTable.load(path)
    rng = np.random.default_rng(0)
    dim = rng.uniform(8, 512, 64)
    rows = rng.uniform(64, 1e6, 64)
    pool = rng.uniform(1, 32, 64)
    np.testing.assert_array_equal(
        synth_table.fwd_lookup_ms(dim, rows, 200, pool),
        loaded.fwd_lookup_ms(dim, rows, 200, pool))
    np.testing.assert_array_equal(
        synth_table.bwd_lookup_ms(dim, rows, 200, pool),
        loaded.bwd_lookup_ms(dim, rows, 200, pool))
    np.testing.assert_array_equal(
        synth_table.comm_ms([0.0, 0.5, 4.0]), loaded.comm_ms([0.0, 0.5, 4.0]))
    assert loaded.version == synth_table.version == CALIBRATION_VERSION
    assert loaded.fingerprint == synth_table.fingerprint
    assert loaded.comm.source == synth_table.comm.source


def test_table_rejects_future_version(synth_table, tmp_path):
    synth_table.version = CALIBRATION_VERSION + 1
    try:
        path = synth_table.save(str(tmp_path / "future.npz"))
    finally:
        synth_table.version = CALIBRATION_VERSION
    with pytest.raises(ValueError, match="version"):
        CalibrationTable.load(path)
    assert load_or_none(path) is None            # tolerant loader


def test_load_or_none_survives_corrupt_artifact(synth_table, tmp_path):
    """An interrupted calibration must read as 're-measure', not crash."""
    path = synth_table.save(str(tmp_path / "cal.npz"))
    with open(path, "r+b") as f:
        f.truncate(100)                          # corrupt the zip container
    assert load_or_none(path) is None
    assert load_or_none(str(tmp_path / "missing.npz")) is None


def test_interp_exact_on_grid_and_clamped_off_grid(synth_table):
    t = synth_table
    # exactly on a grid point -> the stored cell
    got = t.fwd_lookup_ms(64, 4096, 1024, 8)
    assert got == pytest.approx(t.fwd_ms[1, 1, 1, 1])
    # beyond the hull -> clamps to the edge cell
    lo = t.fwd_lookup_ms(1, 1, 1, 1)
    hi = t.fwd_lookup_ms(4096, 1e9, 1e9, 1e6)
    assert lo == pytest.approx(t.fwd_ms[0, 0, 0, 0])
    assert hi == pytest.approx(t.fwd_ms[-1, -1, -1, -1])
    # between grid points -> strictly between the bracketing cells
    mid = t.fwd_lookup_ms(128, 4096, 1024, 8)
    a, b = sorted([t.fwd_ms[1, 1, 1, 1], t.fwd_ms[2, 1, 1, 1]])
    assert a <= mid <= b


def test_table_validates_grids():
    with pytest.raises(ValueError, match="strictly"):
        CalibrationTable(dims=[64, 16], rows=[1], batches=[1], poolings=[1],
                         fwd_ms=np.zeros((2, 1, 1, 1)),
                         bwd_ms=np.zeros((2, 1, 1, 1)),
                         comm=CommModel.from_spec(), fingerprint={})
    with pytest.raises(ValueError, match="shape"):
        CalibrationTable(dims=[16, 64], rows=[1], batches=[1], poolings=[1],
                         fwd_ms=np.zeros((1, 1, 1, 1)),
                         bwd_ms=np.zeros((1, 1, 1, 1)),
                         comm=CommModel.from_spec(), fingerprint={})


# ---- fused multi-table model (v2) --------------------------------------------


def test_fusion_fit_recovers_clean_model():
    """On noise-free samples generated by a model inside the search grid,
    the fit reproduces it (c0 is closed-form; coef/cap grid-searched)."""
    true = FusionModel(overhead_ms=0.2, pipeline_coef=0.33962106564175104,
                       pipeline_cap=2.0, source="measured")
    rng = np.random.default_rng(0)
    singles = [rng.uniform(0.3, 5.0, size=k)
               for k in (2, 2, 3, 4, 4, 6, 8, 8)]
    fused = np.array([true.fused_ms(t) for t in singles])
    fit = FusionModel.fit(singles, fused)
    assert fit.fit_mape < 1e-6
    assert fit.overhead_ms == pytest.approx(true.overhead_ms, rel=1e-6)
    assert fit.pipeline_coef == pytest.approx(true.pipeline_coef, rel=1e-6)
    assert fit.pipeline_cap == true.pipeline_cap
    assert fit.additive_mape > fit.fit_mape
    assert fit.n_samples == len(singles)


def test_fusion_additive_identity(dlrm_pool):
    """The additive model is the exact per-table sum -- and drives the
    fast path in device pricing (bitwise the pre-v2 arithmetic)."""
    add = FusionModel.additive()
    assert add.is_additive
    ts = np.array([0.4, 0.1, 2.5])
    assert add.fused_ms(ts) == float(ts.sum())
    assert not FusionModel.from_spec(PAPER_GPU).is_additive


def test_v2_roundtrip_preserves_fusion(synth_table, tmp_path):
    path = synth_table.save(str(tmp_path / "v2.npz"))
    loaded = CalibrationTable.load(path)
    assert loaded.fusion_fwd == synth_table.fusion_fwd
    assert loaded.fusion_bwd == synth_table.fusion_bwd
    assert loaded.fusion_fwd.source == "synthetic"
    for k, v in synth_table.fusion_sweep.items():
        np.testing.assert_array_equal(loaded.fusion_sweep[k], v)


def test_v1_artifact_loads_additive_with_warning(synth_table, tmp_path,
                                                save_v1_calibration):
    path = str(tmp_path / "v1.npz")
    save_v1_calibration(synth_table, path)
    with pytest.warns(UserWarning, match="ADDITIVE"):
        v1 = CalibrationTable.load(path)
    assert v1.version == 1
    assert v1.fusion_fwd.is_additive and v1.fusion_bwd.is_additive
    assert v1.fusion_fwd.source == "v1-fallback"


@pytest.fixture()
def scratch_compile_cache(tmp_path, monkeypatch):
    """The CLI's ``enable_compile_cache`` pointed at a temporary directory,
    and the process-global cache directory put back afterwards, so no
    later test in this process compiles through a persistent cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from repro import compile_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR",
                        tmp_path / "jax_cache")
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_calibrate_cli_regenerates_v1_artifact(synth_table, tmp_path,
                                               capsys,
                                               save_v1_calibration,
                                               scratch_compile_cache):
    """An existing artifact that predates schema v2 is re-measured, not
    skipped -- and the refreshed artifact carries a measured fusion fit."""
    out = str(tmp_path / "cal.npz")
    save_v1_calibration(synth_table, out)
    argv = ["--out", out, "--dims", "16", "--rows", "128", "--batches", "8",
            "--poolings", "2", "--repeats", "1", "--fused-ks", "2",
            "--fused-per-k", "1", "--pallas", "off"]
    assert calibrate_main(argv) == 0
    assert "re-measuring" in capsys.readouterr().out
    table = CalibrationTable.load(out)
    assert table.version == CALIBRATION_VERSION
    assert table.fusion_fwd.source == "measured"
    # and a second run with the now-current artifact is a no-op
    assert calibrate_main(argv) == 0
    assert "up to date" in capsys.readouterr().out


def test_fusion_pricing_engaged_on_v2(synth_table, tasks20):
    """A v2 table's fusion model actually changes multi-table pricing:
    fused < additive whenever a device holds >= 2 tables (overhead
    amortization), identical on single-table devices."""
    t = tasks20[0]
    a = np.arange(t.n_tables) % t.n_devices
    fused = MeasuredOracle(synth_table, batch_size=1024).evaluate(
        t.raw_features, a, t.n_devices)
    additive = MeasuredOracle(synth_table, batch_size=1024,
                              fusion=False).evaluate(
        t.raw_features, a, t.n_devices)
    assert (fused.fwd_comp < additive.fwd_comp).all()
    assert fused.overall < additive.overall
    one = np.zeros(1, np.int64)
    f1 = MeasuredOracle(synth_table).evaluate(t.raw_features[:1], one, 1)
    a1 = MeasuredOracle(synth_table, fusion=False).evaluate(
        t.raw_features[:1], one, 1)
    np.testing.assert_array_equal(f1.fwd_comp, a1.fwd_comp)


def test_measure_placement_per_table_pooling(dlrm_pool):
    """pooling=None takes each table's own pooling factor from raw."""
    from repro.profiling import measure_placement
    raw = dlrm_pool[:3].copy()
    raw[:, 2] = [2.0, 5.0, 3.0]                      # F.POOLING
    res = measure_placement(raw, np.zeros(3, np.int64), 1, batch_size=4,
                            pooling=None, max_rows=64, repeats=1)
    assert np.isfinite(res.overall) and res.overall > 0
    assert res.fwd_comp[0] > 0 and res.bwd_comp[0] > 0


# ---- comm model --------------------------------------------------------------


def test_fit_alpha_beta_recovers_clean_model():
    p = np.array([0.5, 1.0, 2.0, 4.0, 8.0])
    alpha, beta = fit_alpha_beta(p, 0.3 + 0.25 * p)
    assert alpha == pytest.approx(0.3, abs=1e-9)
    assert beta == pytest.approx(0.25, abs=1e-9)


def test_synthetic_trace_seeded_and_fit_close_to_spec():
    p = np.array([0.25, 0.5, 1.0, 2.0, 4.0, 8.0])
    t1 = synthetic_trace(p, spec=PAPER_GPU, seed=3)
    t2 = synthetic_trace(p, spec=PAPER_GPU, seed=3)
    np.testing.assert_array_equal(t1, t2)
    alpha, beta = fit_alpha_beta(p, t1)
    assert alpha == pytest.approx(PAPER_GPU.comm_overhead_ms, rel=0.2)
    assert beta == pytest.approx(1.0 / PAPER_GPU.a2a_bw_gbs, rel=0.2)


def test_comm_model_zero_payload_is_free():
    m = CommModel.from_spec(PAPER_GPU)
    out = m.comm_ms([0.0, 1.0])
    assert out[0] == 0.0 and out[1] > m.alpha_ms


def test_measure_collapses_subpad_dims_under_pallas():
    """With the Pallas kernel, dims pad to 128 lanes -- sub-128 dims would
    all time the same compiled shape, so the stored dim axis must be the
    padded, deduplicated one (interpret mode stands in for TPU here)."""
    table = CalibrationTable.measure(
        dims=(16, 64, 128), rows=(64,), batches=(4,), poolings=(2,),
        use_pallas=True, warmup=1, repeats=1,
        comm=CommModel.from_spec(PAPER_GPU))
    np.testing.assert_array_equal(table.dims, [128.0])
    assert table.meta["use_pallas"] is True
    assert (table.fwd_ms > 0).all()


# ---- MeasuredOracle ----------------------------------------------------------


def test_measured_oracle_defaults_to_calibrated_batch(synth_table):
    """Default operating point = the table's largest calibrated batch, so
    compute interpolation and comm payload price the same workload."""
    assert MeasuredOracle(synth_table).batch_size == \
        int(synth_table.batches[-1])
    assert MeasuredOracle(synth_table, batch_size=32).batch_size == 32


def test_measured_oracle_protocol(synth_table, tasks20):
    oracle = MeasuredOracle(synth_table, batch_size=1024)
    assert isinstance(oracle, CostOracle)
    assert oracle.mem_capacity_gb == PAPER_GPU.mem_capacity_gb
    t = tasks20[0]
    a = np.arange(t.n_tables) % t.n_devices
    res = oracle.evaluate(t.raw_features, a, t.n_devices)
    assert oracle.num_evaluations == 1
    assert np.isfinite(res.overall) and res.overall > 0
    assert res.fwd_comp.shape == (t.n_devices,)
    assert (res.fwd_comp > 0).all() and (res.bwd_comp > 0).all()
    assert res.cost_features.shape == (t.n_devices, 3)
    # deterministic: same placement, same measurement
    res2 = MeasuredOracle(synth_table, batch_size=1024).evaluate(
        t.raw_features, a, t.n_devices)
    assert res2.overall == res.overall


def test_measured_oracle_from_path(synth_table, tmp_path):
    path = synth_table.save(str(tmp_path / "cal.npz"))
    oracle = MeasuredOracle(path)
    assert oracle.table.version == synth_table.version


def test_measured_oracle_missing_artifact(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CALIBRATION", str(tmp_path / "nope.npz"))
    with pytest.raises(FileNotFoundError, match="calibrate"):
        MeasuredOracle()


def test_measured_oracle_monotone_in_table_count(synth_table, tasks20):
    oracle = MeasuredOracle(synth_table, batch_size=1024)
    t = tasks20[0]
    a = np.arange(t.n_tables) % t.n_devices
    base = oracle.evaluate(t.raw_features[:-1], a[:-1], t.n_devices)
    more = oracle.evaluate(t.raw_features, a, t.n_devices)
    d = a[-1]                                    # device gaining the table
    assert more.fwd_comp[d] > base.fwd_comp[d]
    assert more.bwd_comp[d] > base.bwd_comp[d]
    assert more.overall >= base.overall


def test_measured_oracle_monotone_in_dim(synth_table, tasks20):
    oracle = MeasuredOracle(synth_table, batch_size=1024)
    t = tasks20[0]
    a = np.arange(t.n_tables) % t.n_devices
    small = oracle.evaluate(t.raw_features, a, t.n_devices)
    wide = t.raw_features.copy()
    wide[:, 0] *= 4.0                            # F.DIM
    big = oracle.evaluate(wide, a, t.n_devices)
    assert (big.fwd_comp >= small.fwd_comp).all()
    assert big.overall > small.overall           # comm payload grows too


def test_measured_oracle_single_device_no_comm(synth_table, tasks20):
    oracle = MeasuredOracle(synth_table, batch_size=1024)
    t = tasks20[0]
    res = oracle.evaluate(t.raw_features, np.zeros(t.n_tables, np.int64), 1)
    assert (res.bwd_comm == 0).all() and (res.fwd_comm == 0).all()
    assert res.overall == pytest.approx(res.fwd_comp[0] + res.bwd_comp[0])


def test_measured_oracle_legal(synth_table, tasks20):
    oracle = MeasuredOracle(synth_table)
    t = tasks20[0]
    assert oracle.legal(t.raw_features,
                        np.arange(t.n_tables) % t.n_devices, t.n_devices)
    assert not oracle.legal(t.raw_features * 1e3,
                            np.zeros(t.n_tables, np.int64), 1)


# ---- KernelOracle adapter ----------------------------------------------------


def test_kernel_adapter_matches_measured_oracle(measured_table, tasks20):
    """The adapter must be a pure delegation: same table, same numbers."""
    t = tasks20[0]
    a = np.arange(t.n_tables) % t.n_devices
    kern = KernelOracle(table=measured_table, batch_size=8)
    meas = MeasuredOracle(measured_table, batch_size=8)
    rk = kern.evaluate(t.raw_features, a, t.n_devices)
    rm = meas.evaluate(t.raw_features, a, t.n_devices)
    np.testing.assert_allclose(rk.fwd_comp, rm.fwd_comp, rtol=1e-12)
    np.testing.assert_allclose(rk.bwd_comp, rm.bwd_comp, rtol=1e-12)
    np.testing.assert_allclose(rk.bwd_comm, rm.bwd_comm, rtol=1e-12)
    assert rk.overall == pytest.approx(rm.overall, rel=1e-12)
    assert kern.num_evaluations == 1


def test_kernel_oracle_lazy_calibration_counts():
    oracle = KernelOracle(batch_size=8, pooling=2, max_rows=128, repeats=1)
    assert oracle.num_evaluations == 0           # nothing measured yet
    assert oracle._measured is None              # calibration is lazy


def test_kernel_oracle_grid_covers_widest_tables():
    """prod-pool dims go to 768: the lazy calibration grid must reach
    them, or interpolation edge-clamps and underprices the widest (most
    expensive) tables."""
    grid = KernelOracle()._calibration_grid()
    assert grid["dims"][-1] >= 768
    pallas_grid = KernelOracle(use_pallas=True)._calibration_grid()
    assert pallas_grid["dims"][-1] >= 768
    assert all(d % 128 == 0 for d in pallas_grid["dims"])
    assert KernelOracle(max_dim=256)._calibration_grid()["dims"][-1] == 256


def test_kernel_oracle_with_table_uses_calibrated_batch(synth_table):
    """A supplied table prices compute and comm at ITS operating point
    unless the caller pins one explicitly (mirrors MeasuredOracle)."""
    assert KernelOracle(table=synth_table).measured().batch_size == \
        int(synth_table.batches[-1])
    assert KernelOracle(table=synth_table,
                        batch_size=32).measured().batch_size == 32


# ---- CLI ---------------------------------------------------------------------


def test_calibrate_cli_smoke(tmp_path):
    out = str(tmp_path / "cli" / "cal.npz")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    cmd = [sys.executable, "-m", "repro.profiling.calibrate", "--smoke",
           "--out", out, "--repeats", "1",
           "--dims", "16,64", "--rows", "128", "--poolings", "2"]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=300)
    assert r.returncode == 0, r.stderr
    table = CalibrationTable.load(out)
    assert table.version == CALIBRATION_VERSION
    assert (table.fwd_ms > 0).all() and (table.bwd_ms > 0).all()
    assert table.meta.get("cli") is True
    # second run: artifact matches version/fingerprint/grid -> no-op
    r2 = subprocess.run(cmd, capture_output=True, text=True, env=env,
                        timeout=300)
    assert r2.returncode == 0, r2.stderr
    assert "up to date" in r2.stdout


# ---- trainer end-to-end ------------------------------------------------------


def test_trainer_end_to_end_with_measured_oracle(synth_table, tasks20):
    oracle = MeasuredOracle(synth_table, batch_size=1024)
    agent = DreamShard(tasks20, oracle,
                       DreamShardConfig(n_iterations=2, n_collect=3,
                                        n_cost=4, n_rl=2))
    history = agent.train()
    assert len(history) == 2
    assert oracle.num_evaluations == 6           # n_iterations * n_collect
    assert np.isfinite(history[-1]["cost_loss"])
    t = tasks20[0]
    a = agent.place(t.raw_features, t.n_devices)
    assert a.shape == (t.n_tables,)
    assert oracle.legal(t.raw_features, a, t.n_devices)
    # placements decode hardware-free: no extra oracle evaluations
    assert oracle.num_evaluations == 6


def test_measured_oracle_beats_live_timing_throughput(measured_table,
                                                      tasks20):
    """The acceptance-criterion regression in miniature: interpolation
    must be orders of magnitude faster than one live kernel timing."""
    import time
    from repro.profiling import measure_placement
    t = tasks20[0]
    a = np.arange(t.n_tables) % t.n_devices
    oracle = MeasuredOracle(measured_table, batch_size=8)
    oracle.evaluate(t.raw_features, a, t.n_devices)          # warm numpy
    n = 50
    t0 = time.perf_counter()
    for _ in range(n):
        oracle.evaluate(t.raw_features, a, t.n_devices)
    interp = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    measure_placement(t.raw_features, a, t.n_devices, batch_size=8,
                      pooling=2, max_rows=128, repeats=1)
    live = time.perf_counter() - t0
    assert live / interp > 20          # conservative floor for CI jitter
