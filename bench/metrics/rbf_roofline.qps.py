"""The rbf Pallas kernel's share of its roofline in the closed serving
cell, in %: the least time the chip needs for k(U, S) of every flush of
the window at its padded bucket (ops at the bf16 peak or bytes at the HBM
bandwidth, whichever is longer, from shapes) over the device time of the
kernel's events."""
from bench import flops, serving, traces


def read(ctx):
    cfg = ctx.cell.config
    ops = nbytes = 0.0
    for rows in serving.flush_buckets(ctx.traffic):
        o, b = flops.rbf(rows, cfg["support"], cfg["input_dim"])
        ops, nbytes = ops + o, nbytes + b
    return traces.roofline_percent(ctx, traces.RBF_KERNEL, ops, nbytes)
