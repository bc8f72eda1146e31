"""Device ms per wave of the rank update of the whitened global factor
(the ``chol_update_rank`` program's events in the trace)."""
from bench import traces


def read(ctx):
    waves = ctx.traffic.layer["waves"]
    if ctx.trace is None or not waves:
        return None
    s = ctx.trace.time_s(traces.RANK_UPDATE, line="modules")
    return 1e3 * s / waves if s > 0 else None
