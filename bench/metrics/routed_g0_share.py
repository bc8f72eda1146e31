"""Share of the window's routed flushes that ran the G = 0 program (no
overflow groups), in %, from the plan's counters."""


def read(ctx):
    layer = ctx.traffic.layer
    before, after = layer["before"], layer["after"]
    routed = after["n_routed_batches"] - before["n_routed_batches"]
    if routed <= 0:
        return None
    g0 = after["n_g0_batches"] - before["n_g0_batches"]
    return 100.0 * g0 / routed
