"""Persistent JAX compilation cache for the repo's entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and ``python -m
repro.profiling.calibrate`` call ``enable_compile_cache()`` before they
compile anything, so a later run of the same programs on the same machine
skips compilation.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at a fixed path inside the
checkout, ``<repo>/.jax_cache`` (gitignored): a cache entry is found again
only by a run that looks in the same directory, so the path is never built
from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
