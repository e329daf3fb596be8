"""Share of the Pallas kernels' device time that the work they must do
needs at the chip's peaks: per layer and wave the larger of kept-weight
FLOPs over the peak and minimum bytes over HBM bandwidth
(``harness.work``), summed over the traced waves, over the device time of
every Pallas custom call in the trace.  In %."""
from harness.work import roofline_seconds


def read(ctx):
    if not ctx.traced or ctx.summary.pallas_ns == 0:
        return None
    least = roofline_seconds(ctx.work, ctx.traced_waves(), *ctx.peaks())
    return 100.0 * least / (ctx.summary.pallas_ns / 1e9)
