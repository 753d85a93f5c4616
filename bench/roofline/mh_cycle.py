"""Bytes the collapsed-Gibbs MH cycle needs per sampled token, whatever
implements it (LightLDA's O(1) update; the configuration's 2 cycles).

Per token, once (4-byte cells):
    read  word id, doc id, round-start topic, validity, uniform   5
    write new topic                                               1
Per MH step (2 steps a cycle, one word and one doc proposal):
    read  the alias cell: cut and alias                           2
    read  the table row's total mass U                            1
    read  the proposal masses W at the proposed and current topic 2
    read  C_dk, C_wk and C_k at the proposed and current topic    6
The sub-uniforms are hashed from the token's uniform in registers and
move no bytes.  An implementation that loads whole K-wide rows per token
moves far more; that surplus is what this share shows.  Operations are
a few multiplies and compares per step, so the HBM bytes bound it."""

CYCLES = 2
STEPS = 2 * CYCLES
CELL = 4
PER_TOKEN = 5 + 1
PER_STEP = 2 + 1 + 2 + 6
BYTES_PER_TOKEN = CELL * (PER_TOKEN + STEPS * PER_STEP)      # 200


def bytes_moved(counts: dict) -> float:
    """``counts['tokens']``: tokens sampled in the measured window."""
    return BYTES_PER_TOKEN * counts["tokens"]
