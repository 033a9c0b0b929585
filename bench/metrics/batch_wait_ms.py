"""Mean time a request waited in the service for its batch: from its
hand-over (``arrival_t``) to the start of the ``serve.flush`` that served
it (the program's spans)."""

from program_spans import flushes


def read(ctx):
    waits = [f.t0 - a for f in flushes(ctx.window) for a in f.arrival_t]
    return 1e3 * sum(waits) / len(waits) if waits else None
