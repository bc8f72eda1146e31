"""JAX's persistent compilation cache, placed from outside the program.

The one place a compile cache directory is chosen. Entry points that drive
the chip (``chip_smoke.py``, ``benchmarks/run.py``) call ``enable`` first
thing: a cold serving phase compiles for tens of seconds on a TPU, and a
cache directory that outlives the process turns the next run's compiles
into reads.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads
the variable itself; nothing else is set), otherwise the fixed
``.jax_cache/`` at the root of the checkout (listed in ``.gitignore``). The
path is part of every cache key, so it never holds a temporary name, a
process id or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CacheCounter:
    """Counts persistent-cache lookups while registered: ``hits`` were read
    back, ``misses`` compiled (and were written when they took longer than
    JAX's ``jax_persistent_cache_min_compile_time_secs``)."""

    def __init__(self):
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == _REQUESTS:
            self.requests += 1
        elif event == _HITS:
            self.hits += 1

    @property
    def misses(self) -> int:
        return self.requests - self.hits

    def close(self) -> None:
        jax.monitoring.unregister_event_listener(self._on_event)
