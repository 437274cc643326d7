"""BENCHMARK.json resolves by name to the files that run it."""

import json
import os
import re

import pytest

from bench import run

SPEC = run.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_to_its_files(workload):
    found = run.resolve(SPEC, workload)
    assert found["config"]["name"] == found["cell"]["config"]
    assert os.path.isfile(found["path"])
    with open(found["limits"]) as f:
        assert json.load(f)["limits"]
    for m in found["per_layer"]:
        reader = run._module(found["readers"][m["name"]], "reader")
        assert callable(reader.read)
    e2e = {m["name"] for m in found["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert found["per_layer"]


def test_every_metric_has_a_reader_and_a_cell():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert os.path.isfile(os.path.join(run.ROOT, "bench", "metrics",
                                           f"{m['name']}.py"))
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells


def test_names_units_and_keys_keep_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        with open(os.path.join(run.ROOT, c["file"])) as f:
            file = json.load(f)
        assert set(c["reduced"]) <= set(file)
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"])
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        run.resolve(SPEC, "no.such.cell")
