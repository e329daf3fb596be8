"""Device busy time in the traced window per request served in the trace, in ms."""


def read(ctx):
    if not ctx.traced or not ctx.traced_images:
        return None
    return ctx.summary.busy_ns / 1e6 / ctx.traced_images
