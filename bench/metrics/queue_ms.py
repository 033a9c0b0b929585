"""Mean time a request waited in the service's queue: from its due time to
the start of the search of its batch (benchmark host spans)."""


def read(ctx):
    w = ctx.window
    waits = [(w.batches[r.batch].t0 - r.due) * 1e3 for r in w.in_window()
             if r.batch is not None]
    return sum(waits) / len(waits) if waits else None
