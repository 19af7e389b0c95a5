"""Persistent compilation cache for the repo's entry points.

Every entry point (``chip_smoke.py``, ``repro.launch.serve.main`` and the
``benchmarks/*`` mains) calls ``enable_compile_cache()`` before its first
compile.  Importing the library sets nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself at start-up, so
  nothing is set here and the cache lives only there.
* Otherwise the cache goes to ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed because it is part of the cache
  key: a directory that moved between runs would never hit.

JAX initializes its cache at the first compile of the process, so a
call after that compile has no effect.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir,
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
