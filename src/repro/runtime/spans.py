"""The program's spans: host intervals of its layers, on the profiler's clock.

``with span("sched.flush", tenant=..., n_tickets=...):`` marks one stage of
the program; the keywords are its tags (a ticket, a count). While no
profiler session runs, which is every untraced run, a span asks
``TraceAnnotation.is_enabled()`` and does nothing more: it reads no clock,
builds no record and takes no lock. While one runs (a
``jax.profiler.trace`` window), each span

* opens a ``jax.profiler.TraceAnnotation`` of its name, so it lands on the
  profiler's host plane on the clock of the device ops;
* appends one ``Record`` to a bounded in-memory buffer, with its times on
  ``time.perf_counter``;
* takes as parent the innermost span open on its thread, so a stage's self
  time is its duration less its children's (``self_time``).

A span opened while JAX traces a function (inside ``jit``, ``vmap`` or
``grad``) records nothing: it would time the trace, not the run. Each
backend compile while the profiler runs is recorded as a ``compile`` record
whose parent is the innermost open span, which names the step that
compiled. Records past ``CAPACITY`` are not kept and are counted by
``dropped()``.

Names are ``<layer>.<stage>`` (``sched.``, ``plan.``, ``fit.``,
``online.``); none starts with ``bench.``, the benchmark's own prefix.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import NamedTuple

import jax
from jax.profiler import TraceAnnotation

# a closed serving window writes ~3.1 records a query (submit, result, wait,
# and a 64-row flush's seven): this holds 30 s at ~45,000 queries/s, in
# ~1.5 GB of host memory at ~370 bytes a record
CAPACITY = 1 << 22
COMPILE = "compile"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_enabled = TraceAnnotation.is_enabled


class Record(NamedTuple):
    seq: int           # order of opening, unique in the process
    name: str
    t0: float          # time.perf_counter seconds
    t1: float
    parent: int        # seq of the innermost span open at opening, or -1
    tag: dict


class _Buffer:
    def __init__(self):
        self.lock = threading.Lock()
        self.records: list[Record] = []
        self.dropped = 0
        self.seq = itertools.count()
        self.local = threading.local()

    def open_spans(self) -> list[int]:
        stack = getattr(self.local, "stack", None)
        if stack is None:
            stack = self.local.stack = []
        return stack

    def add(self, record: Record) -> None:
        with self.lock:
            if len(self.records) < CAPACITY:
                self.records.append(record)
            else:
                self.dropped += 1


_BUFFER = _Buffer()


_OFF = contextlib.nullcontext()          # a span while nothing records it


class _Span:
    __slots__ = ("name", "tag", "seq", "parent", "t0", "annotation")

    def __init__(self, name: str, tag: dict):
        self.name, self.tag = name, tag

    def __enter__(self):
        stack = _BUFFER.open_spans()
        self.parent = stack[-1] if stack else -1
        self.seq = next(_BUFFER.seq)
        stack.append(self.seq)
        self.annotation = TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return None

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.annotation.__exit__(*exc)
        _BUFFER.open_spans().pop()
        _BUFFER.add(Record(self.seq, self.name, self.t0, t1, self.parent,
                           self.tag))
        return None


def span(name: str, **tag):
    """A context manager that records ``name`` while the profiler runs."""
    if not _enabled() or not jax.core.trace_ctx.is_top_level():
        return _OFF
    return _Span(name, tag)


def records() -> list[Record]:
    """The records kept since the last ``clear``, in order of opening."""
    with _BUFFER.lock:
        return sorted(_BUFFER.records)


def dropped() -> int:
    """Records not kept since the last ``clear``: the buffer was full."""
    return _BUFFER.dropped


def clear() -> None:
    with _BUFFER.lock:
        _BUFFER.records.clear()
        _BUFFER.dropped = 0


def self_time(recs: list[Record]) -> dict[int, float]:
    """Seconds of each record (by ``seq``) less those of its children
    (compiles included) among ``recs``."""
    own = {r.seq: r.t1 - r.t0 for r in recs}
    for r in recs:
        if r.parent in own:
            own[r.parent] -= r.t1 - r.t0
    return own


def _on_compile(event: str, duration: float, **_) -> None:
    if event != _COMPILE_EVENT or not _enabled():
        return
    stack = _BUFFER.open_spans()
    t1 = time.perf_counter()
    _BUFFER.add(Record(next(_BUFFER.seq), COMPILE, t1 - duration, t1,
                       stack[-1] if stack else -1, {}))


jax.monitoring.register_event_duration_secs_listener(_on_compile)
