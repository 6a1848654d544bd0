"""Persistent compilation cache and compile-time accounting for the entry
points (``launch/serve.py``, ``chip_smoke.py``).

Call :func:`setup` from an entry point, never at import: it decides where
JAX keeps compiled programs across processes.  ``JAX_COMPILATION_CACHE_DIR``
wins when set (JAX reads it itself, so nothing is set in code); otherwise
the cache lives at a fixed ``<checkout>/.jax_cache`` — the path is part of
the cache key, so a directory that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def cache_dir() -> str:
    """Where compiled programs are kept: the env value, else the fixed
    checkout-local directory."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def setup() -> str:
    """Turn the persistent cache on for this process; returns its dir."""
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return cache_dir()


class CompileClock:
    """Seconds spent in XLA backend compiles (a persistent-cache hit
    counts only its read) while the context is open."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def _listen(self, event: str, duration: float, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += duration
            self.compiles += 1

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)
