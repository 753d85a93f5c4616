"""Device ms per iteration under the ``lda.ck_sync`` scope: the ``psum``
that agrees the topic totals ``C_k`` at every round's end, averaged over
the cell's chips."""
from harness.readers import scope_ms_per

SCOPES = ("lda.ck_sync",)


def read(ctx):
    return scope_ms_per(ctx, SCOPES, "iterations")
