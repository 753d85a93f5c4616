"""Device ms per iteration in the Pallas MH kernel: ops under
``jit(mh_cycle_call)/pallas_call`` (``kernels/mh_alias.py``), the name
found in a TPU v5e trace of the ``mh_pallas`` sampler."""
from harness.readers import scope_ms_per

SCOPES = ("jit(mh_cycle_call)/pallas_call",)


def read(ctx):
    return scope_ms_per(ctx, SCOPES, "iterations")
