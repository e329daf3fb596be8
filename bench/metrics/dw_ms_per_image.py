"""Device time under the scopes of the layers in which a
``vsconv_dw_halo_pallas`` op ran, Pallas kernels and every other op (the
halo input each depthwise kernel reads is laid out there), per image served
in the traced window, in ms.  Nothing is read when no such layer ran."""

KERNEL = "vsconv_dw_halo_pallas"


def read(ctx):
    if not ctx.traced or not ctx.traced_images:
        return None
    dw = [lt for n, lt in ctx.summary.layers.items()
          if n is not None and KERNEL in lt.kernels]
    if not dw:
        return None
    return sum(lt.pallas_ns + lt.other_ns for lt in dw) / 1e6 \
        / ctx.traced_images
