"""The rbf Pallas kernel's share of its roofline in the fit cell, in %:
the least time the chip needs for the covariance blocks of the window's
fits (ops at the bf16 peak or bytes at the HBM bandwidth, whichever is
longer, from shapes) over the device time of the kernel's events."""
from bench import flops, traces


def read(ctx):
    cfg = ctx.cell.config
    ops, nbytes = flops.rbf_ppitc_fit(cfg["n"], cfg["machines"],
                                      cfg["support"], cfg["input_dim"])
    fits = ctx.traffic.layer["fits"]
    return traces.roofline_percent(ctx, traces.RBF_KERNEL, fits * ops,
                                   fits * nbytes)
