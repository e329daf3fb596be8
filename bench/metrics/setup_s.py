"""Set-up: process start to the first timed request (server build with
its weights, compilation or loading from the compile cache, warm-up)."""


def read(ctx):
    return ctx.setup_s
