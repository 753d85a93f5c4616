"""The whole ring iteration's share of one chip's peak: the bytes a
collapsed-Gibbs MH iteration needs for one chip's share of the tokens
(``roofline/mh_iteration.py``, over the cell's chips) at the HBM peak,
over the traced window's time on the host clock."""
from harness import common


def read(ctx):
    if ctx.window_s <= 0 or ctx.counts.get("tokens", 0) <= 0:
        return None
    need = common.load_module("roofline", "mh_iteration").bytes_moved(
        ctx.counts) / len(ctx.planes)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / ctx.window_s
