"""Percent of the MH kernel's device time that the bytes the MH cycle
needs (``roofline/mh_cycle.py``) take at the HBM peak."""
from harness.readers import roofline_share

SCOPES = ("jit(mh_cycle_call)/pallas_call",)


def read(ctx):
    return roofline_share(ctx, "mh_cycle", SCOPES)
