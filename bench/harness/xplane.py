"""Reduction of a profiler trace (``.xplane.pb``) to device intervals.

Events and their times come from ``jax.profiler.ProfileData``.  The op
metadata that names where an op came from (``tf_op``, the JAX name stack
such as ``jit(iteration_vmap)/.../jit(build_word_tables)/...``, and
``hlo_category``) sits in each device plane's event metadata, which
``ProfileData`` does not expose; :func:`_device_metadata` reads it from
the file with a minimal protobuf wire-format decoder (the XSpace schema
of ``tsl/profiler/protobuf/xplane.proto``, fields noted inline).

On a TPU plane the ``XLA Ops`` line nests: a ``while`` op spans its
body's ops.  Every time sum here is therefore a union of intervals, never
a sum of durations, so nesting counts once."""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"


# ---------------------------------------------------------------------------
# Minimal protobuf wire-format reading
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes):
    """Yield ``(field_number, wire_type, value)``; length-delimited values
    are ``memoryview`` slices, varints ints, fixed64 raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire == 5:
            val, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, wire, val


def _map_entry_value(entry) -> memoryview:
    for f, _, v in _fields(entry):
        if f == 2:
            return v
    return memoryview(b"")


def _device_metadata(path: str) -> Dict[str, Dict[str, Dict[str, str]]]:
    """``{plane name: {event name: {stat name: value}}}`` for the device
    planes: string- and int-valued stats of each event's metadata."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, _, plane in _fields(space):
        if field != 1:                     # XSpace.planes
            continue
        name, ev_md, st_md = "", [], {}
        for pf, _, pv in _fields(plane):
            if pf == 2:                    # XPlane.name
                name = bytes(pv).decode()
            elif pf == 4:                  # XPlane.event_metadata (map)
                ev_md.append(_map_entry_value(pv))
            elif pf == 5:                  # XPlane.stat_metadata (map)
                md = _map_entry_value(pv)
                sid, sname = 0, ""
                for sf, _, sv in _fields(md):
                    if sf == 1:
                        sid = sv
                    elif sf == 2:
                        sname = bytes(sv).decode()
                st_md[sid] = sname
        if not name.startswith(DEVICE_PREFIX):
            continue
        events = {}
        for md in ev_md:
            ename, stats = "", []
            for ef, _, ev in _fields(md):
                if ef == 2:                # XEventMetadata.name
                    ename = bytes(ev).decode(errors="replace")
                elif ef == 5:              # XEventMetadata.stats
                    stats.append(ev)
            vals = {}
            for st in stats:
                mid, val = 0, None
                for sf, _, sv in _fields(st):
                    if sf == 1:
                        mid = sv
                    elif sf in (3, 4):     # uint64 / int64 value
                        val = str(sv)
                    elif sf == 5:          # str_value
                        val = bytes(sv).decode(errors="replace")
                    elif sf == 7:          # ref_value: a stat name
                        val = st_md.get(sv, str(sv))
                if val is not None:
                    vals[st_md.get(mid, str(mid))] = val
            events[ename] = vals
        out[name] = events
    return out


# ---------------------------------------------------------------------------
# Intervals
# ---------------------------------------------------------------------------

def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(max(0.0, min(e, hi) - max(s, lo))
               for s, e in union(intervals))


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for s, e in union(busy):
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


# ---------------------------------------------------------------------------
# The reduced trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Op:
    name: str          # HLO instruction text, as the trace names the op
    start: float       # ns, on the host's clock
    end: float
    scope: str         # JAX name stack (``tf_op``), or ""
    category: str      # ``hlo_category``, or ""


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]                       # per device plane
    host: List[Tuple[str, float, float]]           # python-thread spans
    modules: Dict[str, List[Tuple[str, float, float]]]   # per plane

    def devices(self) -> List[str]:
        return sorted(self.ops)

    def busy(self, plane: str, lo: float, hi: float) -> float:
        return covered([(o.start, o.end) for o in self.ops[plane]], lo, hi)

    def time_where(self, plane: str, lo: float, hi: float, pred) -> float:
        """Device ns inside ``[lo, hi]`` under ops matching ``pred``."""
        return covered([(o.start, o.end) for o in self.ops[plane]
                        if pred(o)], lo, hi)

    def spans(self, name: str) -> List[Tuple[float, float]]:
        return [(s, e) for n, s, e in self.host if n == name]


def newest_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Reduce one ``.xplane.pb``: every device plane's ``XLA Ops`` with
    their scope, and the spans of the host's Python thread (the host line
    holding most events whose name starts with ``bench.``)."""
    from jax.profiler import ProfileData
    meta = _device_metadata(path)
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Op]] = {}
    modules: Dict[str, List[Tuple[str, float, float]]] = {}
    host_lines = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            md = meta.get(plane.name, {})
            lst = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    m = md.get(ev.name, {})
                    lst.append(Op(ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  m.get("tf_op", ""),
                                  m.get("hlo_category", "")))
            ops[plane.name] = lst
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_lines[line.name] = [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events]
    host_thread = max(host_lines, default=None, key=lambda ln: sum(
        n.startswith("bench.") for n, _, _ in host_lines[ln]))
    return Trace(ops=ops, host=host_lines.get(host_thread, []),
                 modules=modules)


def self_times(ops: Sequence[Op]) -> List[Tuple[Op, float]]:
    """Each op with its self time: its interval less the ops nested in
    it (ops on one line nest as a tree)."""
    order = sorted(ops, key=lambda o: (o.start, -o.end))
    out: List[List] = []
    stack: List[List] = []
    for op in order:
        while stack and stack[-1][0].end <= op.start:
            stack.pop()
        rec = [op, op.end - op.start]
        if stack:
            stack[-1][1] -= op.end - op.start
        stack.append(rec)
        out.append(rec)
    return [(op, max(t, 0.0)) for op, t in out]
