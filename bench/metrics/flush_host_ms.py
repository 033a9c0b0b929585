"""Mean host time of a service flush: ``serve.flush`` less the host's wait
for the device, from the end of ``engine.dispatch`` to the end of
``serve.device_wait`` (the program's spans; ``bench/program_spans.py``)."""

from program_spans import flushes


def read(ctx):
    host = [(f.t1 - f.t0) - (f.ready - f.dispatch_t1) for f in flushes(ctx.window)]
    return 1e3 * sum(host) / len(host) if host else None
