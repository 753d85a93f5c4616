"""Percent of the rotation's time in flight that the bytes one chip must
send through the ring (``roofline/ring_rotate.py``) take at the chip's
inter-chip interconnect peak.  The time is that under the
``lda.rotate`` scope, each asynchronous permute counted from its start
to its done (``harness/collectives.py``), averaged over the chips."""
from harness import common
from harness.collectives import in_flight_s

SCOPES = ("lda.rotate",)


def read(ctx):
    t = in_flight_s(ctx, SCOPES)
    if t <= 0 or ctx.counts.get("iterations", 0) <= 0:
        return None
    need = common.load_module("roofline", "ring_rotate").bytes_moved(
        ctx.counts)
    return 100.0 * 8.0 * need / ctx.peaks["ici_bits_per_s"] / t
