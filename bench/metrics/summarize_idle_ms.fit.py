"""Device-idle ms per fit under the ``online.summarize`` program span (the
runner's per-block map, dispatched eagerly): device 0's idle time inside
those spans, over the window's fits."""
from bench import program_spans


def read(ctx):
    ps = program_spans.load(ctx)
    fits = ctx.traffic.layer["fits"]
    if ps is None or not fits or not ps.count("online.summarize"):
        return None
    return ps.idle_ms_under("online.summarize") / fits
