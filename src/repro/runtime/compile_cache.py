"""Where JAX keeps its persistent compilation cache.

`enable` points the cache at ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, and otherwise at ``.jax_cache`` at the root of the checkout (listed in
``.gitignore``). The directory is part of every entry's key, so it is a
fixed path: never a temporary name, a pid or a time. The entry points call
`enable` (``chip_smoke.py``, `repro.launch.serve`, the examples,
``benchmarks/run.py``); importing the library changes no JAX setting.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
