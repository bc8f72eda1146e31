"""Queue wait in ms, mean over the window's flushed tickets: from the start
of a ticket's ``sched.submit`` program span to the start of the
``sched.flush`` that took it, joined by ticket."""
from bench import program_spans


def read(ctx):
    ps = program_spans.load(ctx)
    waits = ps.queue_wait_ms() if ps else []
    return sum(waits) / len(waits) if waits else None
