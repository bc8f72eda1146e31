"""Host ms per flush, mean over the window's flushes: the time the client
thread spent in the scheduler calls of one flush (the submit or pump that
dispatched it, and the result calls of its queries, blocking waits
included), from the benchmark's spans."""
from bench import serving


def read(ctx):
    ms = serving.host_ms_per_flush(ctx.spans, ctx.traffic.layer)
    return float(ms.mean()) if ms.size else None
