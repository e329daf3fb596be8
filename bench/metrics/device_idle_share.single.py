"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / (window length)."""


def read(ctx):
    return ctx.summary.idle_share if ctx.traced else None
