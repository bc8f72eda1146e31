"""The benchmark's own spans around its calls into the program.

Off (the untraced runs), ``span`` records nothing. On (``--trace 1``), each
span is written into the profiler's trace as a
``jax.profiler.TraceAnnotation`` and kept in memory as (name, start, end,
tag) on the host's ``perf_counter`` clock; the caller may set
``info["tag"]`` on the dict the span yields (a flush index, a count).
"""
from __future__ import annotations

import contextlib
import time

import jax


class Spans:
    def __init__(self, active: bool):
        self.active = active
        self.records: list[tuple[str, float, float, object]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        info = {}
        if not self.active:
            yield info
            return
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield info
        self.records.append((name, t0, time.perf_counter(),
                             info.get("tag")))

    def named(self, name: str) -> list[tuple[float, float, object]]:
        return [(a, b, tag) for n, a, b, tag in self.records if n == name]
