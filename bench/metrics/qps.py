"""Requests completed in the window over its length (host clock)."""


def read(ctx):
    w = ctx.window
    done = [r for r in w.requests if r.done is not None and r.done <= w.t_end]
    return len(done) / w.seconds if done else None
