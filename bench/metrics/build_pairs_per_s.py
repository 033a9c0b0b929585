"""CE pairs scored by bulk_score calls completed in the window, over its
length (host clock)."""


def read(ctx):
    w = ctx.window
    pairs = sum(p for _, t1, p, *_ in w.calls if t1 <= w.t_end)
    return pairs / w.seconds if pairs else None
