"""Mean real requests per searched batch in the window (the rest of the
batch bucket is padding)."""


def read(ctx):
    w = ctx.window
    fills = [b.n_real for b in w.batches if b.t1 <= w.t_end]
    return sum(fills) / len(fills) if fills else None
