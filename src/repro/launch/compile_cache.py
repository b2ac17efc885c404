"""JAX's persistent compilation cache, for the entry points only.

Each entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/run.py``) calls
:func:`enable_compilation_cache` before it compiles anything; importing
the library sets nothing.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this module sets no other directory.  Otherwise the cache lives at one
fixed path inside the checkout, ``<repo>/.jax_cache``: the path is
part of what a later run must find again, so it never depends on a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
