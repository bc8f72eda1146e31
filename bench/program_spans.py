"""The program's own spans (``repro.runtime.spans``) in a traced window, for
the per-layer metrics that read them.

The program records its spans on ``time.perf_counter``, the clock of the
benchmark's own spans; the device ops are on the profiler's clock. The one
span both clocks hold is the window: ``ctx.spans.named("window")`` on
``perf_counter`` and ``ctx.trace.window`` in ns. ``ProgramSpans`` keeps the
records that lie inside the window and maps them onto the trace's clock
with that offset. ``load`` gives None where there is nothing to read: an
untraced run, a program that records no spans, or a buffer that dropped
records (a partial count would read low).
"""
from __future__ import annotations


def load(ctx):
    """The window's ``ProgramSpans``, or None."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    windows = ctx.spans.named("window")
    records = spans.records()
    if ctx.trace is None or not windows or not records or spans.dropped():
        return None
    lo, hi, _ = windows[-1]
    return ProgramSpans(records, (lo, hi), ctx.trace)


def _merged(intervals):
    out: list[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


class ProgramSpans:
    def __init__(self, records, window: tuple[float, float], trace):
        lo, hi = window
        self.records = [r for r in records if lo <= r.t0 and r.t1 <= hi]
        self.trace = trace
        self.offset_ns = trace.window[0] - lo * 1e9

    def named(self, name: str) -> list:
        return [r for r in self.records if r.name == name]

    def count(self, name: str) -> int:
        return len(self.named(name))

    def total_ms(self, name: str) -> float:
        return 1e3 * sum(r.t1 - r.t0 for r in self.named(name))

    def self_ms(self, name: str) -> float:
        """Summed ms of the spans ``name`` less their children's."""
        from repro.runtime.spans import self_time
        own = self_time(self.records)
        return 1e3 * sum(own[r.seq] for r in self.named(name))

    def idle_ms_under(self, name: str, device: int = 0) -> float:
        """Device ms in which no op ran, inside the spans ``name``."""
        lo, hi = self.trace.window
        spans = _merged((max(lo, r.t0 * 1e9 + self.offset_ns),
                         min(hi, r.t1 * 1e9 + self.offset_ns))
                        for r in self.named(name))
        spans = [s for s in spans if s[1] > s[0]]
        length = sum(b - a for a, b in spans)
        busy = _overlap(spans, self.trace.busy_intervals(device))
        return 1e-6 * (length - busy)

    def queue_wait_ms(self) -> list[float]:
        """Per flushed ticket of the window, ms from the start of its
        ``sched.submit`` to the start of the ``sched.flush`` that took it
        (joined by tenant and ticket)."""
        submitted = {(r.tag["tenant"], r.tag["ticket"]): r.t0
                     for r in self.named("sched.submit")}
        waits = []
        for f in self.named("sched.flush"):
            first, n = f.tag["first_ticket"], f.tag["n_tickets"]
            for ticket in range(first, first + n):
                t0 = submitted.get((f.tag["tenant"], ticket))
                if t0 is not None:
                    waits.append(1e3 * (f.t0 - t0))
        return waits
