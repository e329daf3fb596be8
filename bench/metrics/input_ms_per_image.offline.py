"""Input path per image in the traced calls, in ms: the ``backend.stack``
spans (the host batch built image by image) and ``backend.put`` spans (its
transfer to the device) over the images the waves computed.  From the
program's spans (``harness.program_spans``)."""
from harness.program_spans import totals


def read(ctx):
    t = totals(ctx)
    if not t or not t["images"]:
        return None
    return (t["backend.stack"] + t["backend.put"]) * 1e3 / t["images"]
