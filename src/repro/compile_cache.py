"""JAX's persistent compilation cache, kept at one fixed place.

A compiled program is found again only where the cache path matches, so
the path is derived from this package's location: never from the working
directory, a temporary name, a process id or the time. The entry points
(``launch.serve``, ``launch.train``, ``benchmarks.run``, ``chip_smoke.py``)
call :func:`enable` before they compile anything.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.cache/jax-compile`` — ``.cache/`` is git-ignored
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".cache" / "jax-compile"


def enable() -> Path:
    """Turn the cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this sets
    nothing; otherwise the cache goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
