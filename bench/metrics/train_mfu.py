"""The whole iteration's share of the chip's peak: the bytes a
collapsed-Gibbs MH iteration needs (``roofline/mh_iteration.py``) at the
HBM peak, over the traced window's time on the host clock."""
from harness import common


def read(ctx):
    if ctx.window_s <= 0 or ctx.counts.get("tokens", 0) <= 0:
        return None
    need = common.load_module("roofline", "mh_iteration").bytes_moved(
        ctx.counts)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / ctx.window_s
