"""The whole step's share of the chip's peak: kept-weight FLOPs per image
times the images served in the traced window, over the window's length
and the peak for the configuration's dtype.  In %."""


def read(ctx):
    if not ctx.traced or not ctx.traced_images:
        return None
    flops = sum(lw.flops for lw in ctx.work) * ctx.traced_images
    return 100.0 * flops / (ctx.summary.window_ns / 1e9) / ctx.peaks()[0]
