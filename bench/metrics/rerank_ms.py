"""Mean time from a flush's last adaptive round (its R-th ``ce.round``
mark) to its results ready (the end of ``serve.device_wait``): the rerank
sweep, its CE pairs and the final top-k."""

from program_spans import flushes


def read(ctx):
    t = [f.ready - f.marks[f.rounds - 1] for f in flushes(ctx.window)
         if f.rounds >= 1 and len(f.marks) >= f.rounds]
    return 1e3 * sum(t) / len(t) if t else None
