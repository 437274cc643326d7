"""The trace reduction: interval arithmetic, scopes, and a recorded
trace of a small placed step on one v5e chip."""

import os

import pytest

from bench import trace_reduce as tr
from bench.program import LOOKUP_SCOPE, UPDATE_SCOPE

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_uncovered_length():
    a = tr._union([(5, 9), (0, 2), (1, 3)])
    assert a == [[0, 3], [5, 9]]
    assert tr._length(a) == 7
    b = tr._union([(1, 2), (6, 7), (8, 20)])
    assert tr._minus(a, b) == 7 - 1 - 1 - 1


@pytest.mark.parametrize("op, op_name, scope", [
    ("fusion.2", f"jit(step)/jvp({LOOKUP_SCOPE})/jit(_take)/gather",
     "emb_fwd"),
    ("fusion.4", f"jit(step)/transpose(jvp({LOOKUP_SCOPE}))/scatter-add",
     "emb_bwd"),
    ("fusion.9", f"jit(step)/{UPDATE_SCOPE}/mul", "emb_bwd"),
    ("all_to_all.6", f"jit(step)/jvp({LOOKUP_SCOPE})/shard_map/all_to_all",
     "a2a"),
    ("all-to-all-done.1", "", "a2a"),
    ("fusion.7", "jit(step)/jvp()/dot_general", "other"),
])
def test_classify(op, op_name, scope):
    assert tr.classify(op, op_name, LOOKUP_SCOPE, UPDATE_SCOPE) == scope


def test_hlo_op_names_reads_metadata():
    text = ('  %fusion.3 = bf16[4]{0} fusion(%p), kind=kLoop, calls=%f, '
            'metadata={op_name="jit(step)/jvp(x)/gather" source_file="a"}\n'
            '  ROOT %tuple.1 = (f32[]) tuple(%a)\n')
    assert tr.hlo_op_names(text) == {"fusion.3": "jit(step)/jvp(x)/gather"}


@pytest.fixture(scope="module")
def small():
    """Two steps of a 4-table placed step (batch 2048, uniform ids) on one
    v5e chip, with the op names of its compiled program."""
    import json
    with open(os.path.join(HERE, "data", "small_step.op_names.json")) as f:
        names = json.load(f)
    return tr.reduce_trace(tr.load(os.path.join(HERE, "data",
                                                "small_step.xplane.pb")),
                           names, LOOKUP_SCOPE, UPDATE_SCOPE)


def test_recorded_trace_busy_scopes_and_gaps(small):
    (chip,) = small.chips
    assert chip.name == "TPU:0"
    assert 0 < chip.busy_s < small.window_s < 0.01
    gaps = sum(s for _, s in chip.gaps)
    assert abs(chip.busy_s + gaps - small.window_s) < 1e-9
    assert {span for span, _ in chip.gaps} <= set(tr.HOST_SPANS)
    assert chip.scope_s["emb_bwd"] > chip.scope_s["emb_fwd"] > 0
    assert chip.scope_s.get("a2a", 0.0) == 0.0 and chip.a2a_exposed_s == 0
    assert sum(chip.scope_s.values()) >= chip.busy_s - 1e-12
    top = small.top_ops(3)
    assert top[0][0].startswith("fusion.") and "[emb_bwd]" in top[0][0]
    assert 0 < small.worst_idle_share() < 1


def test_metric_readers_on_the_recorded_trace(small):
    from bench import run, work
    from bench.peaks import peaks
    ctx = {"summary": small, "steps": 2, "chips": 1,
           "peaks": peaks("TPU v5 lite"), "step_s": small.window_s / 2,
           "work": work.Work(flops=1e9, emb_fwd_bytes=1e6,
                             emb_bwd_bytes=1e6, step_bytes=4e6)}
    read = {m: run._module(os.path.join(run.ROOT, "bench", "metrics",
                                        f"{m}.py"), m).read
            for m in ("emb_fwd_roofline", "emb_bwd_roofline", "step_mfu",
                      "a2a_exposed_ms", "device_idle.step")}
    fwd = read["emb_fwd_roofline"](ctx)
    assert fwd == pytest.approx(100 * 2e6 / 819e9
                                / small.scope_s("emb_fwd"))
    assert 0 < read["emb_bwd_roofline"](ctx) < fwd
    assert read["step_mfu"](ctx) == pytest.approx(
        100 * max(1e9 / 197e12, 4e6 / 819e9) / (small.window_s / 2))
    assert read["a2a_exposed_ms"](ctx) is None
    assert read["device_idle.step"](ctx) == pytest.approx(
        100 * small.worst_idle_share())
