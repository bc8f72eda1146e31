"""Host ms per flush of the per-ticket unpack: the summed ``sched.unpack``
program spans of the window (storing each ticket's answer) over its
``sched.flush`` spans."""
from bench import program_spans


def read(ctx):
    ps = program_spans.load(ctx)
    flushes = ps.count("sched.flush") if ps else 0
    return ps.total_ms("sched.unpack") / flushes if flushes else None
