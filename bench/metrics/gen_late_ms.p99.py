"""How late the load generator ran: the 99th percentile, over the window's
queries, of submit time minus due time, in ms (host clock). A starved
generator shows here, not as a fast server."""
import numpy as np


def read(ctx):
    a = ctx.traffic.layer["answers"]
    late = (a.view("sub") - a.view("due")) * 1e3
    late = late[np.isfinite(late)]
    return float(np.percentile(late, 99)) if late.size else None
