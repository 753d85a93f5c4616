"""Tokens sampled in the window's whole iterations, over the window's
seconds, per chip."""


def read(run):
    return run.counts["tokens"] / run.window_s / run.chips
