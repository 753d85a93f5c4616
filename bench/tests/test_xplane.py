"""The trace reduction on hand-made intervals and on a small trace
recorded on a TPU v5e (``tools/record_test_trace.py``)."""
import os

import pytest

from harness import readers, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = os.path.join(DATA, "tiny_tpu.xplane.pb")


def test_union_merges_nested_and_overlapping():
    assert xplane.union([(5, 9), (0, 10), (2, 3), (12, 14), (13, 15)]) == [
        (0, 10), (12, 15)]
    assert xplane.covered([(0, 10), (2, 3), (8, 12)], 1, 11) == 10


def test_gaps_are_the_complement_inside_the_window():
    assert xplane.gaps([(2, 4), (3, 5), (8, 9)], 0, 10) == [
        (0, 2), (5, 8), (9, 10)]
    assert xplane.gaps([], 0, 3) == [(0, 3)]


def test_self_time_subtracts_nested_ops_once():
    op = lambda name, s, e: xplane.Op(name, s, e, "", "")  # noqa: E731
    ops = [op("while", 0, 100), op("a", 10, 30), op("b", 40, 90),
           op("b1", 50, 60), op("c", 120, 130)]
    got = {o.name: t for o, t in xplane.self_times(ops)}
    assert got == {"while": 30, "a": 20, "b": 40, "b1": 10, "c": 10}
    assert sum(got.values()) == xplane.covered(
        [(o.start, o.end) for o in ops], 0, 200)


def _tiny():
    if not os.path.exists(TINY):
        pytest.fail(f"missing recorded trace {TINY}")
    return xplane.load(TINY)


def test_recorded_trace_names_scopes_and_spans():
    tr = _tiny()
    assert tr.devices() == ["/device:TPU:0"]
    ops = tr.ops["/device:TPU:0"]
    assert any("scope_a" in o.scope for o in ops)
    assert any("scope_b" in o.scope for o in ops)
    assert len(tr.spans("bench.window")) == 1
    assert len(tr.spans("bench.step")) == 6
    mods = {n.split("(")[0] for n, _, _ in tr.modules["/device:TPU:0"]}
    assert mods == {"jit_step_a", "jit_step_b"}


def test_recorded_trace_idle_and_breakdown():
    tr = _tiny()
    lo, hi = tr.spans("bench.window")[0]
    ctx = readers.Context(trace=tr, planes=["/device:TPU:0"], lo=lo, hi=hi,
                          peaks={}, counts={"iterations": 3})
    busy = ctx.busy_s()
    assert 0 < busy < ctx.window_s
    # three 20 ms host sleeps leave the device idle at least that long
    assert ctx.window_s - busy > 0.06
    idle = readers.idle_share(ctx)
    assert 0 < idle < 100
    assert ctx.module_s("jit_step_a") > 0
    assert readers.scope_ms_per(ctx, ("scope_a",), "iterations") > 0
    bd = readers.breakdown(ctx)
    gaps = dict(bd["idle_gaps"])
    assert max(gaps, key=gaps.get) == "bench.sleep"
    assert gaps["bench.sleep"] >= 0.06
    total_ops = sum(t for _, t in bd["device_ops"])
    assert total_ops <= busy + 1e-9
