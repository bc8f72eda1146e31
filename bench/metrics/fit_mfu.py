"""The fit's share of the bf16 peak of the cell's chips, in %: the
operations of one whitened pPITC fit (from shapes) times fits per second of
the window, over the cell's chips times one chip's peak."""
from bench import flops


def read(ctx):
    if ctx.peaks is None:
        return None
    cfg = ctx.cell.config
    layer = ctx.traffic.layer
    ops = flops.ppitc_fit(cfg["n"], cfg["machines"], cfg["support"],
                          cfg["input_dim"])
    return 100.0 * ops * layer["fits"] / layer["elapsed"] / (
        ctx.cell.chips * ctx.peaks["flops_bf16_per_s"])
