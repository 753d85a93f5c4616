"""Device ms per iteration in the ring's worker rounds: ops whose JAX
name stack holds ``lda.sample`` (the round's gathers, the MH kernel and
the count writes), averaged over the cell's chips."""
from harness.readers import scope_ms_per

SCOPES = ("lda.sample",)


def read(ctx):
    return scope_ms_per(ctx, SCOPES, "iterations")
