"""Share of the depthwise kernel's device time that its layers' work needs
at the chip's peaks: per depthwise layer and wave the larger of kept-tap
FLOPs over the peak and minimum bytes over HBM bandwidth
(``harness.work``), summed over the traced waves and over the layers under
whose scope a ``vsconv_dw_halo_pallas`` op ran, over the device time of
the Pallas ops under those scopes.  Nothing is read when no such layer ran,
or when their number differs from the ``dw_convs`` the program recorded on
a traced ``backend.launch`` span (a program that records none reads
nothing).  In %."""
from harness.program_spans import match
from harness.work import roofline_seconds

KERNEL = "vsconv_dw_halo_pallas"


def _launch_counts(ctx) -> set:
    """``dw_convs`` of every traced ``backend.launch`` span."""
    try:
        from repro.launch import spans
    except ImportError:
        return set()
    return {s.attrs.get("dw_convs")
            for _, under in match(spans.recorded(), ctx.record.calls,
                                  len(ctx.traced_calls()), spans.CAPACITY)
            for s in under if s.name == "backend.launch"}


def read(ctx):
    if not ctx.traced:
        return None
    dw = {n for n, lt in ctx.summary.layers.items()
          if n is not None and KERNEL in lt.kernels}
    if not dw or _launch_counts(ctx) != {len(dw)}:
        return None
    least = roofline_seconds([lw for lw in ctx.work if lw.name in dw],
                             ctx.traced_waves(), *ctx.peaks())
    pallas_ns = sum(ctx.summary.layers[n].pallas_ns for n in dw)
    return 100.0 * least / (pallas_ns / 1e9)
