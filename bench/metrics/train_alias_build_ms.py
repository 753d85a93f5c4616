"""Device ms per iteration in the MH proposal-table builds: ops whose JAX
name stack lies under ``build_word_tables`` or ``build_doc_tables``
(``core/mh.py``, building through ``core/alias.py``)."""
from harness.readers import scope_ms_per

SCOPES = ("jit(build_word_tables)", "jit(build_doc_tables)")


def read(ctx):
    return scope_ms_per(ctx, SCOPES, "iterations")
