"""Share of the Pallas kernels' device time that the work they must do
needs at the chip's peaks: per layer and wave the larger of kept-weight
FLOPs over the peak and minimum bytes over HBM bandwidth
(``harness.work``), summed over the traced waves and over the layers under
whose scope a Pallas kernel ran, over the device time of every Pallas
custom call in the trace.  A layer the program runs through XLA alone (the
dense stem) is not counted.  Nothing is read when a Pallas kernel ran
outside every layer's scope.  In %."""
from harness.work import roofline_seconds


def read(ctx):
    if not ctx.traced or ctx.summary.pallas_ns == 0:
        return None
    ran = ctx.summary.pallas_layers()
    if ran is None:
        return None
    least = roofline_seconds([lw for lw in ctx.work if lw.name in ran],
                             ctx.traced_waves(), *ctx.peaks())
    return 100.0 * least / (ctx.summary.pallas_ns / 1e9)
