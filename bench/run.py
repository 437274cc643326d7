"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload dlrm50.step_uniform --seed 7 --seconds 10 \
        --trace 0

``BENCHMARK.json`` at the checkout's root names the cells.  A cell's
``config`` is found as ``bench/configs/<config>.json``, its ``traffic``
as ``bench/traffic/<traffic>.json``, the traffic names its path
(``bench/paths/<path>.py``), each per-layer metric is read by
``bench/metrics/<metric>.py`` and the limits of the correctness check
are ``bench/limits/<workload>.json``.

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of two whole steps.  The run fails, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str, root: str = ROOT) -> dict:
    """Everything a cell needs, found by the names in ``spec``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = _json(os.path.join(root, "bench", "traffic",
                                 f"{cell['traffic']}.json"))

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "path": os.path.join(root, "bench", "paths",
                             f"{traffic['path']}.py"),
        "limits": os.path.join(root, "bench", "limits", f"{workload}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
        "readers": {m["name"]: os.path.join(root, "bench", "metrics",
                                            f"{m['name']}.py")
                    for m in spec["per_layer"] if applies(m)},
    }


def check_device(chips: int):
    """The devices of the cell; exits 2 without a TPU or enough chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform={devs[0].platform})",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        sys.exit(2)
    return devs[:chips]


def enable_cache() -> None:
    """The program's persistent compile cache, holding every program."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result_line(out: dict, found: dict, trace: bool) -> dict:
    metrics = {}
    if trace:
        for m in found["per_layer"]:
            reader = _module(found["readers"][m["name"]], "bench_metric")
            value = reader.read(out["layer_ctx"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in found["end_to_end"]:
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    device = dict(out["device"], memory_peak_bytes=out["memory_peak_bytes"])
    if trace:
        device.update(busy_s=out["busy_s"], window_s=out["window_s"])
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    found = resolve(load_spec(), args.workload)
    devices = check_device(found["cell"]["chips"])
    enable_cache()
    path = _module(found["path"], "bench_path")
    cell = path.make_cell(found["config"], found["traffic"], devices)
    out = path.run(cell, args.seed, args.seconds, bool(args.trace), T_START,
                   _json(found["limits"])["limits"])
    line = result_line(out, found, bool(args.trace))
    for text in out["info"]:
        print(text, flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
