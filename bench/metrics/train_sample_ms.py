"""Device ms per iteration in the worker round: ops whose JAX name stack
holds ``lda.sample`` (``core/engine/backends.py``), the round's gathers,
the MH kernel and the count writes."""
from harness.readers import scope_ms_per

SCOPES = ("lda.sample",)


def read(ctx):
    return scope_ms_per(ctx, SCOPES, "iterations")
