"""Where JAX keeps its persistent compilation cache, set once per process.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX reads
it and this module sets nothing. Otherwise the cache goes to one fixed,
git-ignored directory of the checkout (``.jax_cache/``). The directory is
part of what a cached program is found by, so it never depends on a temp
name, a pid or the time: a second run of the same program on the same
checkout reads back what the first one wrote.

`chip_smoke.py` and the launch CLIs call `enable_compile_cache` before
their first compile.
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
