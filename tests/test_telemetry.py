"""Telemetry subsystem self-tests.

The contract the rest of the stack leans on: a true no-op disabled
path (shared singleton span, untouched registry, bitwise-identical
oracle results), correct nested-span parenting per thread, lossless
counter increments under thread contention, and sink round-trips
(Chrome trace schema, JSONL, ``trace_to``, the report CLI).
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro import telemetry as tele
from repro.telemetry import core
from repro.telemetry.report import main as report_main
from repro.sim.costsim import CostSimulator


# ---- disabled path ----------------------------------------------------------


def test_disabled_span_is_shared_noop_singleton():
    assert not tele.is_enabled()
    sp = tele.span("x", a=1)
    assert sp is tele.NOOP_SPAN
    assert sp is tele.span("y")                 # one object, zero alloc
    with sp as inner:
        assert inner.set(b=2) is inner          # set() is a no-op too


def test_disabled_count_and_gauge_touch_nothing():
    assert not tele.is_enabled()
    tele.count("t10.never", 5)
    tele.gauge("t10.never_g", 1.0)
    snap = tele.snapshot()
    assert snap["enabled"] is False
    assert "t10.never" not in snap["counters"]
    assert "t10.never_g" not in snap["gauges"]
    assert tele.counter_value("t10.never") == 0


def test_noop_path_does_not_change_oracle_results(dlrm_pool, rng):
    """Instrumented code must be bitwise-identical with telemetry off
    and on -- spans observe, they never participate."""
    from repro.api import SimOracle
    raw = dlrm_pool[:8]
    A = rng.integers(0, 4, size=(6, 8))

    def _costs():
        oracle = SimOracle(CostSimulator(seed=0))
        out = [r.overall for r in oracle.evaluate_many(raw, A, 4)]
        out.append(oracle.evaluate(raw, A[0], 4).overall)
        return np.asarray(out)

    assert not tele.is_enabled()
    off = _costs()
    tele.enable()
    try:
        on = _costs()
    finally:
        tele.reset()
        tele.disable()
    np.testing.assert_array_equal(off, on)


# ---- spans and counters -----------------------------------------------------


def test_nested_span_parenting(telemetry):
    with telemetry.span("outer") as outer:
        with telemetry.span("inner") as inner:
            assert inner.parent == outer.id
        with telemetry.span("inner2") as inner2:
            pass
    with telemetry.span("root2") as root2:
        pass
    events = {e[0]: e for e in telemetry.get_tracer().snapshot_events()}
    assert set(events) == {"outer", "inner", "inner2", "root2"}
    # tuple layout: (name, ts_us, dur_us, tid, span_id, parent_id, args)
    assert events["outer"][5] is None
    assert events["inner"][5] == events["outer"][4]
    assert events["inner2"][5] == events["outer"][4]
    assert events["root2"][5] is None
    assert inner2.parent == outer.id and root2.parent is None
    # children are recorded before (inside) their parent, with tighter spans
    assert events["inner"][1] >= events["outer"][1]
    assert events["inner"][2] <= events["outer"][2]


def test_span_set_attrs_and_aggregates(telemetry):
    with telemetry.span("work", phase="a") as sp:
        sp.set(result=42)
    (event,) = telemetry.get_tracer().snapshot_events()
    assert event[6] == {"phase": "a", "result": 42}
    aggs = telemetry.get_tracer().span_aggregates()
    assert aggs["work"]["count"] == 1
    assert aggs["work"]["total_ms"] >= 0
    assert telemetry.snapshot()["spans"]["work"]["count"] == 1


def test_counter_atomicity_under_threads(telemetry):
    n_threads, n_incr = 8, 10_000

    def _worker():
        for _ in range(n_incr):
            telemetry.count("t10.contended")

    threads = [threading.Thread(target=_worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert telemetry.counter_value("t10.contended") == n_threads * n_incr


def test_spans_from_threads_get_distinct_tids(telemetry):
    def _worker():
        with telemetry.span("threaded"):
            pass

    t = threading.Thread(target=_worker)
    with telemetry.span("mainline"):
        pass
    t.start()
    t.join()
    tids = {e[3] for e in telemetry.get_tracer().snapshot_events()}
    assert len(tids) == 2


def test_event_cap_counts_drops():
    tracer = core.Tracer(max_events=3)
    for i in range(5):
        with core.Span(tracer, f"s{i}", {}):
            pass
    assert len(tracer.snapshot_events()) == 3 and tracer.dropped == 2


def test_registry_survives_disable_then_reset_clears(telemetry):
    telemetry.count("t10.kept", 2)
    telemetry.disable()
    assert telemetry.counter_value("t10.kept") == 2     # export-after-run
    telemetry.reset()
    assert telemetry.counter_value("t10.kept") == 0
    telemetry.enable()                                  # fixture teardown


# ---- sinks ------------------------------------------------------------------


def _record_sample(telemetry):
    with telemetry.span("parent", kind="demo") as sp:
        with telemetry.span("child"):
            pass
        sp.set(rows=3)
    telemetry.count("t10.calls", 3)
    telemetry.gauge("t10.level", 0.5)


def test_chrome_trace_schema(telemetry, tmp_path):
    _record_sample(telemetry)
    path = telemetry.write_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        payload = json.load(f)
    events = payload["traceEvents"]
    assert [e["name"] for e in events] == ["parent", "child"]
    for e in events:
        assert e["ph"] == "X" and e["cat"] == "repro"
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float))
        assert "span_id" in e["args"]
    child = next(e for e in events if e["name"] == "child")
    parent = next(e for e in events if e["name"] == "parent")
    assert child["args"]["parent_id"] == parent["args"]["span_id"]
    assert parent["args"]["rows"] == 3
    other = payload["otherData"]
    assert other["counters"]["t10.calls"] == 3
    assert other["gauges"]["t10.level"] == 0.5
    assert other["dropped_events"] == 0


def test_jsonl_roundtrip_and_load_trace(telemetry, tmp_path):
    _record_sample(telemetry)
    jl = telemetry.write_jsonl(str(tmp_path / "trace.jsonl"))
    ch = telemetry.write_chrome_trace(str(tmp_path / "trace.json"))
    parsed = telemetry.read_jsonl(jl)
    assert parsed["meta"]["schema"] == 1
    assert [s["name"] for s in parsed["spans"]] == ["child", "parent"]
    child, parent = parsed["spans"]
    assert child["parent"] == parent["id"]
    assert parsed["counters"] == {"t10.calls": 3}
    assert parsed["gauges"] == {"t10.level": 0.5}
    # load_trace sniffs both formats into the same shape
    for path in (jl, ch):
        trace = telemetry.load_trace(path)
        assert {s["name"] for s in trace["spans"]} == {"parent", "child"}
        assert trace["counters"]["t10.calls"] == 3


def test_trace_to_none_is_transparent():
    assert not tele.is_enabled()
    with tele.trace_to(None) as tracer:
        assert tracer is None and not tele.is_enabled()


def test_trace_to_exports_and_restores_state(tmp_path, capsys):
    assert not tele.is_enabled()
    out = str(tmp_path / "run.jsonl")
    with tele.trace_to(out):
        assert tele.is_enabled()
        with tele.span("body"):
            pass
    assert not tele.is_enabled()                # restored the default
    assert "[telemetry] wrote 1 span(s)" in capsys.readouterr().out
    assert [s["name"] for s in tele.read_jsonl(out)["spans"]] == ["body"]
    tele.reset()


def test_report_cli_smoke(telemetry, tmp_path, capsys):
    _record_sample(telemetry)
    path = telemetry.write_jsonl(str(tmp_path / "trace.jsonl"))
    assert report_main([path, "--top", "5"]) == 0
    out = capsys.readouterr().out
    assert "parent" in out and "t10.calls" in out and "gauges:" in out


def test_summarize_reports_drops(telemetry):
    trace = {"meta": {"dropped_events": 7}, "spans": [], "counters": {},
             "gauges": {}}
    assert "7 span(s) dropped" in telemetry.summarize(trace)


def test_write_without_tracer_raises(tmp_path):
    assert not tele.is_enabled()
    with pytest.raises(RuntimeError, match="not enabled"):
        tele.write_chrome_trace(str(tmp_path / "x.json"))


# ---- the profiler's clock ---------------------------------------------------


def test_enabled_spans_reach_the_profiler_host_plane(telemetry, tmp_path):
    """Each enabled span opens a ``jax.profiler.TraceAnnotation`` of its
    name: a profiler trace shows it on a host plane, beside its args'
    recording in the tracer."""
    import glob
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("probe.host_plane", rows=3):
            jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    planes = {plane.name for plane in
              jax.profiler.ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name == "probe.host_plane"}
    assert planes and all(p.startswith("/host:") for p in planes)
    names = [e[0] for e in telemetry.get_tracer().snapshot_events()]
    assert names == ["probe.host_plane"]


def test_annotation_closes_with_its_span_and_disabled_path_stays_noop():
    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    tracer = core.Tracer(annotation=Annotation)
    with pytest.raises(ValueError):
        with tracer.span("outer", {}):
            with tracer.span("inner", {}):
                raise ValueError
    assert log == [("enter", "outer"), ("enter", "inner"),
                   ("exit", "inner"), ("exit", "outer")]
    assert not tele.is_enabled()
    assert tele.span("x") is tele.NOOP_SPAN


def test_telemetry_imports_and_records_without_jax():
    """jax is imported on the first ``enable()``, not on import; where it
    does not import, spans record all the same."""
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro import telemetry as tele\n"
            "assert tele.span('x') is tele.NOOP_SPAN\n"
            "tracer = tele.enable()\n"
            "with tele.span('y'):\n"
            "    pass\n"
            "assert tracer.annotation is None\n"
            "assert [e[0] for e in tracer.snapshot_events()] == ['y']\n"
            "print('OK')\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code],
                       env={**os.environ, "PYTHONPATH": src},
                       capture_output=True, text=True, timeout=120)
    assert r.stdout.strip() == "OK", r.stderr
