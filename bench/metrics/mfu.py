"""Whole-step model FLOPs utilization: the CE FLOPs of the work completed
in the window (the budget's pairs of every real request served, or every
bulk pair scored) over the window's length times the chip's bf16 peak.
Padded batch rows are not counted: they are not work a user asked for."""


def read(ctx):
    w = ctx.window
    if w.calls:
        pairs = sum(p for _, t1, p, *_ in w.calls if t1 <= w.t_end)
    else:
        done = [r for r in w.requests if r.done is not None and r.done <= w.t_end]
        pairs = len(done) * ctx.engine["budget"]
    if not pairs:
        return None
    flops = pairs * ctx.flops_per_pair
    return 100.0 * flops / (w.seconds * ctx.peaks["bf16_flops_per_s"])
