"""The fit's share of the chip's bf16 peak, in %: the operations of one
whitened pPITC fit (from shapes) times fits per second of the window."""
from bench import flops


def read(ctx):
    if ctx.peaks is None:
        return None
    cfg = ctx.cell.config
    layer = ctx.traffic.layer
    ops = flops.ppitc_fit(cfg["n"], cfg["machines"], cfg["support"],
                          cfg["input_dim"])
    return 100.0 * ops * layer["fits"] / layer["elapsed"] / ctx.peaks[
        "flops_bf16_per_s"]
