"""JAX's persistent compilation cache, placed once by each entry point.

Only entry points call :func:`enable_compile_cache` (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``); importing the library never
touches the cache. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here overrides it. Otherwise the cache sits at one fixed
directory inside the checkout, ``artifacts/jax_cache`` (gitignored): the
path is part of what makes a later run find the entries again, so it is
never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

#: The checkout's root (src/repro/launch/ -> three levels up).
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / "artifacts" / "jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
