"""The served step's share of the bf16 peak of the cell's chips, in %:
the operations of one pPITC query (from shapes) times the queries answered
per second of the window, over the cell's chips times one chip's peak."""
from bench import flops


def read(ctx):
    if ctx.peaks is None:
        return None
    cfg = ctx.cell.config
    qps = ctx.traffic.end_to_end()["queries_per_s"]
    ops = flops.pitc_query(cfg["support"], cfg["input_dim"])
    return 100.0 * ops * qps / (
        ctx.cell.chips * ctx.peaks["flops_bf16_per_s"])
