"""Readings of collectives in a device trace.  On the TPU a collective
may run asynchronously: a ``*-start`` op launches it, other ops run while
it is in flight, and a ``*-done`` op waits for it.  The ops' own
intervals then show only the time the chip waited; the transfer itself
lasts from the start op to the end of the done op that consumes it."""
from __future__ import annotations

import re

from harness import xplane
from harness.readers import Context

_OPCODE = re.compile(r"\)?\s([\w-]+)\(")
_START_REF = re.compile(r"%([\w.-]*-start[\w.-]*)")


def _instr(op: xplane.Op) -> str:
    """The op's HLO instruction name, without its ``%``."""
    return op.name.split(" = ", 1)[0].strip().lstrip("%")


def _opcode(op: xplane.Op) -> str:
    """The op's HLO opcode, from its text or else its instruction name
    (``collective-permute-start.2`` names a ``collective-permute-start``)."""
    m = _OPCODE.search(op.name.split(" = ", 1)[-1])
    return m.group(1) if m else _instr(op).split(".")[0]


def _started_by(done: xplane.Op) -> str:
    """The start op a done op consumes: its operand, or else the start
    named like it."""
    m = _START_REF.search(done.name.split(" = ", 1)[-1])
    return m.group(1) if m else _instr(done).replace("-done", "-start")


def in_flight_s(ctx: Context, needles) -> float:
    """Seconds of the window, averaged over the cell's chips, in which an
    op whose name stack holds any of ``needles`` runs, or an asynchronous
    collective that such an op started is in flight."""
    tot = 0.0
    for plane in ctx.planes:
        ops = sorted((o for o in ctx.trace.ops[plane]
                      if any(n in o.scope for n in needles)),
                     key=lambda o: o.start)
        started = {}
        iv = []
        for op in ops:
            code = _opcode(op)
            if code.endswith("-start"):
                started.setdefault(_instr(op), []).append(op)
                continue
            pending = (started.get(_started_by(op), [])
                       if code.endswith("-done") else [])
            if pending:
                iv.append((pending.pop(0).start, op.end))
            else:
                iv.append((op.start, op.end))
        iv += [(o.start, o.end) for lst in started.values() for o in lst]
        tot += xplane.covered(iv, ctx.lo, ctx.hi)
    return tot / len(ctx.planes) / 1e9
