"""Where JAX keeps its persistent compilation cache for this repo's
entry points (``chip_smoke.py``, ``benchmarks/``).

Called by those entry points, never at library import, so the tests
keep the cache off.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set here; otherwise the cache goes to one fixed
directory inside the checkout — a fixed path, because the path is part
of the cache key.
"""

from __future__ import annotations

import os

import jax

#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Point the persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
