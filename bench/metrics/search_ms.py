"""Mean host-clock time of one engine search, dispatch to results ready
(``AdaCURRetriever.search`` + ``block_until_ready``), over the window's
batches."""


def read(ctx):
    w = ctx.window
    t = [(b.t1 - b.t0) * 1e3 for b in w.batches if b.t1 <= w.t_end]
    return sum(t) / len(t) if t else None
