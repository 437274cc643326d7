"""bench/run.py refuses to run without a TPU, and without the program."""

import os
import shutil
import subprocess
import sys

from bench import run

ARGS = ["--workload", "dlrm50.step_uniform", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, os.path.join(root, "bench",
                                                        "run.py"), *ARGS],
                          cwd=root, env=env, capture_output=True,
                          text=True, timeout=120)


def test_run_on_a_cpu_exits_nonzero_and_prints_no_result():
    out = _run(run.ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
