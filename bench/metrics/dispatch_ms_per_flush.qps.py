"""Host ms per flush of the dispatch: the summed ``sched.dispatch`` program
spans of the window (pad, enqueue of the serve executable, trim) over its
``sched.flush`` spans."""
from bench import program_spans


def read(ctx):
    ps = program_spans.load(ctx)
    flushes = ps.count("sched.flush") if ps else 0
    return ps.total_ms("sched.dispatch") / flushes if flushes else None
