"""The program's own scopes (``dlrm.*``, ``emb.*``) as the trace
reduction sees them: every op keeps the class ``classify`` gives it, and
a small placed step recorded on one v5e chip with those scopes in its op
names has each scope and almost all of its busy time under them."""

import contextlib
import json
import os
import re
from unittest import mock

import pytest

from bench import trace_reduce as tr
from bench.program import LOOKUP_SCOPE, UPDATE_SCOPE

HERE = os.path.dirname(os.path.abspath(__file__))
_WRAPPED = re.compile(r"[\w.\-]+\((.*)\)")

ONE_CHIP_SCOPES = ("dlrm.bottom", "dlrm.embed", "dlrm.interact", "dlrm.top",
                   "dlrm.loss", "dlrm.emb_update", "dlrm.dense_update",
                   "emb.lookup")


def scopes_of(op_name: str) -> set:
    """The scope names in ``op_name``'s path, unwrapped from the
    transformations around them: ``a/transpose(jvp(b))/c`` -> a, b, c."""
    out = set()
    for seg in re.split(r"[/;]", op_name):
        while (m := _WRAPPED.fullmatch(seg)):
            seg = m.group(1)
        out.add(seg)
    return out


def seconds_under(summary, op_names: dict, *scopes: str) -> list:
    """Per chip, device seconds of the ops whose ``op_name`` holds one of
    ``scopes``; an op is the first word of a ``Chip.op_s`` key."""
    want = set(scopes)
    return [sum(s for key, s in c.op_s.items()
                if want & scopes_of(op_names.get(key.split()[0], "")))
            for c in summary.chips]


@pytest.mark.parametrize("op_name, scopes", [
    ("jit(step)/transpose(jvp(dlrm.embed))/bench_emb_lookup/emb.lookup/"
     "jit(_take)/scatter-add",
     {"step", "dlrm.embed", "bench_emb_lookup", "emb.lookup", "_take",
      "scatter-add"}),
    ("jit(step)/jvp(dlrm.top)/dot_general;jit(step)/dlrm.loss/neg",
     {"step", "dlrm.top", "dot_general", "dlrm.loss", "neg"}),
    ("", {""}),
])
def test_scopes_of_unwraps_whole_segments(op_name, scopes):
    assert scopes_of(op_name) == scopes
    assert "dlrm.interaction" not in scopes_of("jit(step)/dlrm.interact/add")


@pytest.fixture(scope="module")
def tiny_step_op_names():
    """Op names of a tiny benchmark step compiled with the program's
    scopes and with them turned off (the benchmark's own kept)."""
    import jax
    from repro.embedding import sharded as E
    from repro.models import dlrm
    from bench import program
    from bench.tests.test_bench_reference import tiny_cell
    cell = tiny_cell("bfloat16")
    program_scopes = (*dlrm.SCOPES, E.LOOKUP_SCOPE, E.EXCHANGE_SCOPE)
    named = jax.named_scope

    def scope(name):
        if name in program_scopes:
            return contextlib.nullcontext()
        return named(name)

    out = {}
    for off in (False, True):
        with mock.patch.object(jax, "named_scope",
                               scope if off else named):
            step = program.make_step(cell.prog, cell.shard)
            out[off] = tr.hlo_op_names(cell.compile(step).as_text())
    return out


def test_program_scopes_keep_every_op_in_its_class(tiny_step_op_names):
    scoped, bare = tiny_step_op_names[False], tiny_step_op_names[True]
    assert scoped.keys() == bare.keys()
    assert scoped != bare
    classes = {op: tr.classify(op, scoped[op], LOOKUP_SCOPE, UPDATE_SCOPE)
               for op in scoped}
    assert classes == {op: tr.classify(op, bare[op], LOOKUP_SCOPE,
                                       UPDATE_SCOPE) for op in bare}
    assert set(classes.values()) == {"emb_fwd", "emb_bwd", "other"}


@pytest.fixture(scope="module")
def recorded():
    """The small step of ``small_step.xplane.pb``, recorded again on a v5e
    chip with the program's own scopes in its op names: (summary, names)."""
    with open(os.path.join(HERE, "data",
                           "small_scoped_step.op_names.json")) as f:
        names = json.load(f)
    summary = tr.reduce_trace(tr.load(os.path.join(
        HERE, "data", "small_scoped_step.xplane.pb")), names, LOOKUP_SCOPE,
        UPDATE_SCOPE)
    return summary, names


@pytest.mark.parametrize("scope", ONE_CHIP_SCOPES)
def test_recorded_scoped_trace_finds_each_scope(recorded, scope):
    summary, names = recorded
    (secs,) = seconds_under(summary, names, scope)
    assert 0 < secs < summary.chips[0].busy_s


def test_recorded_scoped_trace_scopes_cover_the_busy_time(recorded):
    summary, names = recorded
    (chip,) = summary.chips
    (covered,) = seconds_under(summary, names, *ONE_CHIP_SCOPES)
    assert covered >= 0.95 * chip.busy_s
    assert seconds_under(summary, names, "emb.exchange") == [0.0]
    (embed,) = seconds_under(summary, names, "dlrm.embed")
    (lookup,) = seconds_under(summary, names, "emb.lookup")
    assert embed >= lookup
    assert chip.scope_s["emb_bwd"] > chip.scope_s["emb_fwd"] > 0


@pytest.mark.parametrize("name", ["emb_fwd_roofline", "emb_bwd_roofline",
                                  "step_mfu", "device_idle.step"])
def test_accepted_readers_read_the_scoped_trace(recorded, name):
    """The benchmark's scopes still reach its readers through the
    program's."""
    from bench import run, work
    from bench.peaks import peaks
    summary, _ = recorded
    ctx = {"summary": summary, "steps": 2, "chips": 1,
           "peaks": peaks("TPU v5 lite"), "step_s": summary.window_s / 2,
           "work": work.Work(flops=1e9, emb_fwd_bytes=1e6,
                             emb_bwd_bytes=1e6, step_bytes=4e6)}
    read = run._module(os.path.join(run.ROOT, "bench", "metrics",
                                    f"{name}.py"), name).read
    assert 0 < read(ctx) < 100
