"""Host time per image in the traced calls, in ms: each ``serve`` call's
``scheduler.serve`` span less the ``backend.wait`` spans inside it (where
the host only waits for the device), summed, over the images its waves
computed (``backend.wave`` ``images``, occupied slots).  From the
program's spans (``harness.program_spans``)."""
from harness.program_spans import SERVE, totals


def read(ctx):
    t = totals(ctx)
    if not t or not t["images"]:
        return None
    return (t[SERVE] - t["backend.wait"]) * 1e3 / t["images"]
