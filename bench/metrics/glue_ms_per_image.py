"""Device time of the operations that are not Pallas kernels (pads,
phase splits, layout copies, pools), per image served in the traced
window, in ms."""


def read(ctx):
    if not ctx.traced or not ctx.traced_images:
        return None
    return ctx.summary.other_ns / 1e6 / ctx.traced_images
