"""What per-layer metric files share: the traced run's context and the
readings common to several metrics.  A reading that finds nothing to
read returns ``None`` and the metric is left out of the result line."""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

from harness import common, xplane


@dataclasses.dataclass
class Context:
    trace: xplane.Trace
    planes: List[str]          # the device planes the cell uses
    lo: float                  # traced window on the host clock, ns
    hi: float
    peaks: Dict[str, float]    # the device's row of peaks.json
    counts: Dict[str, float]   # what the run counted (tokens, batches...)
    batch_log: List[dict] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_s(self) -> float:
        """Seconds an op ran, averaged over the cell's chips."""
        return sum(self.trace.busy(p, self.lo, self.hi)
                   for p in self.planes) / len(self.planes) / 1e9

    def scoped_s(self, pred: Callable[[xplane.Op], bool]) -> float:
        return sum(self.trace.time_where(p, self.lo, self.hi, pred)
                   for p in self.planes) / len(self.planes) / 1e9

    def module_s(self, prefix: str) -> float:
        """Seconds of device programs whose name starts with ``prefix``,
        averaged over the cell's chips."""
        tot = 0.0
        for p in self.planes:
            iv = [(s, e) for n, s, e in self.trace.modules.get(p, [])
                  if n.startswith(prefix)]
            tot += xplane.covered(iv, self.lo, self.hi)
        return tot / len(self.planes) / 1e9


def idle_share(ctx: Context) -> Optional[float]:
    """Percent of the window in which no op ran on the device."""
    if ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s() / ctx.window_s)


def scope_ms_per(ctx: Context, needles, per: str) -> Optional[float]:
    """Device ms under ops whose JAX name stack holds any of ``needles``,
    per unit of ``ctx.counts[per]``; ``None`` when no op matches."""
    t = ctx.scoped_s(lambda op: any(n in op.scope for n in needles))
    n = ctx.counts.get(per, 0)
    if t <= 0 or n <= 0:
        return None
    return 1e3 * t / n


def roofline_share(ctx: Context, kernel: str, needles) -> Optional[float]:
    """Percent of the kernel's device time that its bytes need at the HBM
    peak (``bench/roofline/<kernel>.py``)."""
    t = ctx.scoped_s(lambda op: any(n in op.scope for n in needles))
    if t <= 0:
        return None
    need = common.load_module("roofline", kernel).bytes_moved(ctx.counts)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / t


def _short(op: xplane.Op) -> str:
    if not op.scope:
        head = op.name.split(" = ", 1)[-1].split("(", 1)[0].split()
        kind = head[-1] if head else (op.category or op.name[:40])
        return f"(unscoped) {kind}"
    parts = op.scope.rstrip(":").split("/")
    fns = [p for p in parts if "jit(" in p]
    return "/".join(([fns[-1]] if fns else []) + [parts[-1]])


def breakdown(ctx: Context, top: int = 10) -> dict:
    """The device ops that took most self time, and the longest idle gaps
    by the innermost host span open at their middle."""
    plane = ctx.planes[0]
    ops = [o for o in ctx.trace.ops[plane]
           if o.end > ctx.lo and o.start < ctx.hi]
    by_op: Dict[str, float] = {}
    for op, t in xplane.self_times(ops):
        by_op[_short(op)] = by_op.get(_short(op), 0.0) + t
    by_gap: Dict[str, float] = {}
    # the harness's spans and the runtime's host events, not the Python
    # tracer's per-call events (``$file:line name``)
    host = sorted((h for h in ctx.trace.host if not h[0].startswith("$")),
                  key=lambda h: (h[1], -h[2]))
    gaps = xplane.gaps([(o.start, o.end) for o in ops], ctx.lo, ctx.hi)
    open_, i = [], 0
    for s, e in gaps:                      # sorted; spans nest per thread
        mid = 0.5 * (s + e)
        while i < len(host) and host[i][1] <= mid:
            open_.append(host[i])
            i += 1
        live = [h for h in open_ if h[2] > mid]
        open_ = live
        name = (min(live, key=lambda h: h[2] - h[1])[0] if live
                else "(no host span)")
        by_gap[name] = by_gap.get(name, 0.0) + (e - s)

    def rank(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_op), "idle_gaps": rank(by_gap)}


def foldin_ms_per_batch(ctx: Context) -> Optional[float]:
    t = ctx.module_s("jit__fold_in")
    n = ctx.counts.get("batches", 0)
    if t <= 0 or n <= 0:
        return None
    return 1e3 * t / n


def batch_occupancy(ctx: Context) -> Optional[float]:
    sizes = [b["size"] for b in ctx.batch_log]
    if not sizes:
        return None
    return 100.0 * sum(sizes) / len(sizes) / ctx.counts["max_batch"]
