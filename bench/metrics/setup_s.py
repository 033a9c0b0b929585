"""Set-up: process start to the window's start (loading, data and weights
made on the device, compilation or cache loads, warm-up)."""


def read(ctx):
    return ctx.setup_s
