"""Mean time of one adaptive engine round on the device: the interval
between consecutive ``ce.round`` marks of a flush's adaptive rounds 1..R
(the marks of the CE-round callbacks; the R + 1st, if any, is the
rerank's)."""

from program_spans import flushes


def read(ctx):
    gaps = [b - a for f in flushes(ctx.window) if len(f.marks) >= f.rounds
            for a, b in zip(f.marks[:f.rounds - 1], f.marks[1:f.rounds])]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
