"""Bytes a whole collapsed-Gibbs MH training iteration needs per token:
the MH cycle (``mh_cycle.py``) plus the fold of the token's move into the
counts, a read-modify-write of C_wk, C_dk and C_k at the old and the new
topic (12 cells).  Proposal tables are per-round state amortised over
every token of a row and not counted, so a faster table build shows as
a higher share."""

CYCLES = 2
STEPS = 2 * CYCLES
CELL = 4
PER_TOKEN = 5 + 1 + 12
PER_STEP = 2 + 1 + 2 + 6
BYTES_PER_TOKEN = CELL * (PER_TOKEN + STEPS * PER_STEP)      # 248


def bytes_moved(counts: dict) -> float:
    """``counts['tokens']``: tokens sampled in the measured window."""
    return BYTES_PER_TOKEN * counts["tokens"]
