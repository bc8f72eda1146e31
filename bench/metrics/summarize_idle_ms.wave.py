"""Device-idle ms per wave under the ``online.summarize`` program span (the
runner's per-block map over the wave's rows, dispatched eagerly): device
0's idle time inside those spans, over the window's waves."""
from bench import program_spans


def read(ctx):
    ps = program_spans.load(ctx)
    waves = ctx.traffic.layer["waves"]
    if ps is None or not waves or not ps.count("online.summarize"):
        return None
    return ps.idle_ms_under("online.summarize") / waves
