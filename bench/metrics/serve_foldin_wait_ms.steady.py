"""Host time blocked on the device's fold-in result per dispatched batch:
the window's time under ``foldin.fetch`` spans (``core/infer.py``), in
ms."""
from harness.spans import span_s

SPAN = "foldin.fetch"


def read(ctx):
    t = span_s(ctx, SPAN)
    n = ctx.counts.get("batches", 0)
    if t is None or n <= 0:
        return None
    return 1e3 * t / n
