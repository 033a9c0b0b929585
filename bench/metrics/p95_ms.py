"""95th percentile latency of every request due in the window, from its due
time to its answer (host clock), including those answered after the close."""

import numpy as np

PERCENTILE = 95


def latencies_ms(ctx):
    return [(r.done - r.due) * 1e3 for r in ctx.window.in_window() if r.done is not None]


def read(ctx, q=PERCENTILE):
    lat = latencies_ms(ctx)
    return float(np.percentile(lat, q)) if lat else None
