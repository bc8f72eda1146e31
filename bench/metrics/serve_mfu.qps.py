"""The served step's share of the chip's bf16 peak, in %: the operations
of one pPITC query (from shapes) times the queries answered per second of
the window."""
from bench import flops


def read(ctx):
    if ctx.peaks is None:
        return None
    cfg = ctx.cell.config
    qps = ctx.traffic.end_to_end()["queries_per_s"]
    ops = flops.pitc_query(cfg["support"], cfg["input_dim"])
    return 100.0 * ops * qps / ctx.peaks["flops_bf16_per_s"]
