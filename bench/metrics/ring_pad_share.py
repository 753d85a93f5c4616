"""Percent of the token slots an iteration samples that are padding:
``1 − real_tokens / slots``, from the engine's counters.  Every (worker,
block) token group is padded to the largest, so a vocabulary cut into
equal id ranges under Zipf word frequencies pads most slots."""


def read(ctx):
    slots = ctx.counts.get("slots", 0)
    real = ctx.counts.get("real_tokens", 0)
    if slots <= 0 or real <= 0:
        return None
    return 100.0 * (1.0 - real / slots)
