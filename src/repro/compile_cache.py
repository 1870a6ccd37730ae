"""Persistent XLA compilation cache for the entry points.

`enable_compile_cache` is called once by each program that drives the chip
(`repro` CLI, `python -m repro.launch.serve`, `chip_smoke.py`), before its
first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing else is set. Otherwise the cache lives in one fixed
directory of the checkout, ``.jax_cache/`` (listed in ``.gitignore``), so
a later run of the same program finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
