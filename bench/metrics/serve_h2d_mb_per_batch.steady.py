"""Bytes that fold-in copied from the host to the device per dispatched
batch (``FoldInResult.h2d_bytes``, summed into each ``batch_log``
entry), in MB (10^6 B)."""
from harness.spans import log_sum


def read(ctx):
    h2d = log_sum(ctx, "h2d_bytes")
    if h2d is None:
        return None
    return h2d / len(ctx.batch_log) / 1e6
