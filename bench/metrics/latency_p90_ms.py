"""90th percentile of request latency over every request of the window
(MLPerf SingleStream's percentile): one request in flight, timed from when
it was sent to when ``serve`` returned its logits."""
from harness.latency import percentile_ms


def read(ctx):
    return percentile_ms(ctx.record, 90)
