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

``compile_counter()`` counts the process's compiles as JAX reports them
through ``jax.monitoring``: backend compiles with their seconds (a load
from the persistent cache is one of them) and persistent-cache hits.
The batched engine starts it on its first call, so a caller that times
a stretch of engine calls can ask how many compiles fell inside it.
"""
from __future__ import annotations

import bisect
import os
import threading
import time

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


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Compiles of this process since ``since`` (the
    ``time.perf_counter()`` at which the counter was made): the time at
    which each ended, their seconds, and the persistent-cache hits."""

    def __init__(self):
        self._lock = threading.Lock()
        self.since = time.perf_counter()
        self.ends: list[float] = []
        self.compile_s = 0.0
        self.cache_hits = 0

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.compile_s += secs
                self.ends.append(time.perf_counter())

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def compiles_between(self, t0: float, t1: float) -> int:
        """Compiles that ended in [t0, t1] (``time.perf_counter()``)."""
        with self._lock:
            return (bisect.bisect_right(self.ends, t1)
                    - bisect.bisect_left(self.ends, t0))


_COUNTER: CompileCounter | None = None
_INSTALL = threading.Lock()


def compile_counter() -> CompileCounter:
    """The process's compile counter; the first call installs its
    ``jax.monitoring`` listeners, later calls return the same one."""
    global _COUNTER
    with _INSTALL:
        if _COUNTER is None:
            counter = CompileCounter()
            jax.monitoring.register_event_duration_secs_listener(
                counter._on_duration)
            jax.monitoring.register_event_listener(counter._on_event)
            _COUNTER = counter
    return _COUNTER
