"""Readings of the program's own tracing (``src/repro/tracing.py``): its
host spans in the window's trace and the counters it adds to the
scheduler's ``batch_log``.  A program that has no such span or counter
gives ``None``, and the metric is left out of the result line."""
from __future__ import annotations

from typing import Optional

from harness import xplane
from harness.readers import Context


def span_s(ctx: Context, name: str) -> Optional[float]:
    """Seconds of the window covered by host spans ``name``, or ``None``
    when the window holds none."""
    iv = [(s, e) for s, e in ctx.trace.spans(name)
          if e > ctx.lo and s < ctx.hi]
    if not iv:
        return None
    return xplane.covered(iv, ctx.lo, ctx.hi) / 1e9


def mean_span_ms(ctx: Context, name: str) -> Optional[float]:
    """Mean whole duration of the spans ``name`` that start in the
    window, in ms."""
    d = [e - s for s, e in ctx.trace.spans(name) if ctx.lo <= s < ctx.hi]
    if not d:
        return None
    return sum(d) / len(d) / 1e6


def log_sum(ctx: Context, key: str) -> Optional[float]:
    """The sum of ``key`` over the window's ``batch_log``, or ``None`` when
    the log is empty or its entries lack ``key``."""
    if not ctx.batch_log or any(key not in b for b in ctx.batch_log):
        return None
    return float(sum(b[key] for b in ctx.batch_log))


def unscoped_s(ctx: Context) -> Optional[float]:
    """Device seconds in ops with an empty name stack, less any part that
    an op with a name stack covers (a ``while`` spans its body's ops), or
    ``None`` when the window holds no op without a name stack."""
    if not any(not o.scope and o.end > ctx.lo and o.start < ctx.hi
               for p in ctx.planes for o in ctx.trace.ops[p]):
        return None
    return ctx.busy_s() - ctx.scoped_s(lambda op: bool(op.scope))
