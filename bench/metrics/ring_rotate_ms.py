"""Device ms per iteration under the ``lda.rotate`` scope
(``core/engine/backends.py``): the ring's ``ppermute`` of each round's
block, its id and its word table, averaged over the cell's chips.  An
asynchronous permute counts only where its own ops run: the time the
chip waits for the ring."""
from harness.readers import scope_ms_per

SCOPES = ("lda.rotate",)


def read(ctx):
    return scope_ms_per(ctx, SCOPES, "iterations")
