#!/usr/bin/env python3
"""Run one cell as ``bench/run.py`` does, print its result line, and write
what the program's own spans, scopes and counters show about the run to
``.bench_traces/reports/<workload>-<seed>-t<trace>.json``:

    python3 bench/tools/span_report.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1> [--rehearse]

With ``--trace 1`` the report holds, beside the result line: the device
time under each ``lda.*`` scope and in unscoped ops per iteration, the
share of busy time those cover, the longest unscoped ops by their HLO
text; the serving window's idle time split by the innermost program
span open across it, the share of fold-in device time inside
``serve.batch`` spans, and each host span's total.  Every run reports
the cost of one span with the profiler off and the mean batch wall time
from ``batch_log`` (``t_finish - t_dispatch``)."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from harness import common, xplane  # noqa: E402

PREFIX = "lda."
OUTSIDE = "(outside serve.batch)"


def span_off_ns(n: int = 200_000) -> Dict[str, float]:
    """Host ns per span with the profiler off: bare, and with the stats a
    ``serve.batch`` span takes."""
    from repro import tracing
    out = {}
    for label, args in (("bare", {}), ("with_args", {
            "size": 8, "bucket": (8, 128), "epoch": 0, "first": 1})):
        t = time.perf_counter()
        for _ in range(n):
            with tracing.span(tracing.SERVE_BATCH, **args):
                pass
        out[label] = (time.perf_counter() - t) / n * 1e9
    t = time.perf_counter()
    for _ in range(n):
        pass
    out["empty_loop"] = (time.perf_counter() - t) / n * 1e9
    return out


def host_spans(tr: xplane.Trace, lo: float, hi: float):
    """The program's spans (``repro.tracing.SPANS``) that meet the window."""
    from repro import tracing
    names = set(tracing.SPANS)
    return [(n, s, e) for n, s, e in tr.host
            if n in names and e > lo and s < hi]


def span_totals(spans, lo, hi) -> Dict[str, Tuple[int, float]]:
    out: Dict[str, List] = {}
    for n, s, e in spans:
        c = out.setdefault(n, [0, []])
        c[0] += 1
        c[1].append((s, e))
    return {n: (k, xplane.covered(iv, lo, hi) / 1e9)
            for n, (k, iv) in sorted(out.items())}


def idle_by_span(gaps, spans) -> Dict[str, float]:
    """Seconds of idle gaps under the innermost program span open across
    each part of them; parts under no span go to ``OUTSIDE``."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)}
                  | {t for s, e in gaps for t in (s, e)})
    out: Dict[str, float] = {}
    gi = 0
    spans = sorted(spans, key=lambda h: h[1])
    si, live = 0, []
    for a, b in zip(cuts, cuts[1:]):
        mid = 0.5 * (a + b)
        while gi < len(gaps) and gaps[gi][1] <= mid:
            gi += 1
        if gi >= len(gaps) or not gaps[gi][0] <= mid < gaps[gi][1]:
            continue
        while si < len(spans) and spans[si][1] <= mid:
            live.append(spans[si])
            si += 1
        live = [h for h in live if h[2] > mid]
        name = (min(live, key=lambda h: h[2] - h[1])[0] if live
                else OUTSIDE)
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def overlap_s(a, b, lo, hi) -> float:
    """Seconds of ``[lo, hi]`` covered by both interval sets."""
    return (xplane.covered(a, lo, hi) + xplane.covered(b, lo, hi)
            - xplane.covered(list(a) + list(b), lo, hi)) / 1e9


def serve_report(tr, planes, lo, hi, n_batches) -> dict:
    spans = host_spans(tr, lo, hi)
    rep = {"spans": span_totals(spans, lo, hi)}
    if not planes:
        return rep
    plane = planes[0]
    ops = [(o.start, o.end) for o in tr.ops[plane]]
    gaps = xplane.gaps(ops, lo, hi)
    rep["idle_s"] = sum(e - s for s, e in gaps) / 1e9
    rep["idle_by_span_s"] = idle_by_span(gaps, spans)
    mods = [(s, e) for n, s, e in tr.modules.get(plane, [])
            if n.startswith("jit__fold_in")]
    batches = [(s, e) for n, s, e in spans if n == "serve.batch"]
    t_mod = xplane.covered(mods, lo, hi) / 1e9
    rep["foldin_module_s"] = t_mod
    rep["foldin_inside_batch_share"] = (
        overlap_s(mods, batches, lo, hi) / t_mod if t_mod else None)
    rep["batches"] = n_batches
    return rep


def train_report(tr, planes, lo, hi, iters, top: int = 24) -> dict:
    if not planes:
        return {"spans": span_totals(host_spans(tr, lo, hi), lo, hi)}
    plane = planes[0]
    ops = [o for o in tr.ops[plane] if o.end > lo and o.start < hi]
    iv = [(o.start, o.end) for o in ops]
    busy = xplane.covered(iv, lo, hi)
    scopes = sorted({p for o in ops for p in o.scope.split("/")
                     if p.startswith(PREFIX)})
    per_scope = {s: xplane.covered([(o.start, o.end) for o in ops
                                    if f"/{s}/" in f"/{o.scope}"], lo, hi)
                 / 1e6 / iters for s in scopes}
    lda = [(o.start, o.end) for o in ops if PREFIX in o.scope]
    unscoped = [(o.start, o.end) for o in ops if not o.scope]
    scoped = [(o.start, o.end) for o in ops if o.scope]
    unscoped_only = busy - xplane.covered(scoped, lo, hi)
    named = xplane.covered(lda + unscoped, lo, hi)
    rest = [(o, t) for o, t in xplane.self_times(ops)
            if o.scope and PREFIX not in o.scope]
    by_self = sorted(xplane.self_times(ops), key=lambda ot: -ot[1])
    return {
        "spans": span_totals(host_spans(tr, lo, hi), lo, hi),
        "iterations": iters,
        "busy_ms_per_iter": busy / 1e6 / iters,
        "scope_ms_per_iter": per_scope,
        "unscoped_ms_per_iter": unscoped_only / 1e6 / iters,
        "lda_or_unscoped_share": named / busy if busy else None,
        "outside_lda_self_ms_per_iter": sum(t for _, t in rest) / 1e6 / iters,
        "outside_lda_top": [[o.scope, o.name[:200], t / 1e6 / iters]
                            for o, t in sorted(rest, key=lambda r: -r[1])
                            [:top]],
        "unscoped_top": [[o.name[:400], o.category, t / 1e6 / iters]
                         for o, t in by_self if not o.scope][:top],
        "ops_top": [[o.scope, o.name[:200], t / 1e6 / iters]
                    for o, t in by_self[:top]],
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.argv = sys.argv[:1]
    import importlib

    import run as bench_run
    _, cell, cfg, mix = bench_run.load_cell(args.workload)
    if args.rehearse:
        cfg, _ = bench_run.rehearsal(cfg, mix)
    jax = common.start_jax(args.rehearse, cell["chips"])
    report = {"workload": args.workload, "seed": args.seed,
              "trace": args.trace, "span_off_ns": span_off_ns()}

    runner = importlib.import_module(f"harness.{cfg['kind']}")
    seen = {}
    run_fn, ctx_fn = runner.run, bench_run.per_layer_context

    def run(*a, **kw):
        seen["run"] = run_fn(*a, **kw)
        seen["trace_dir"] = kw.get("trace_dir")
        return seen["run"]

    def per_layer_context(*a, **kw):
        seen["ctx"] = ctx_fn(*a, **kw)
        return seen["ctx"]

    runner.run = run
    bench_run.per_layer_context = per_layer_context
    result, notes = bench_run.execute(args, jax)
    print(json.dumps(result), flush=True)

    r = seen["run"]
    report["result"] = result
    report["notes"] = dict(notes)
    if r.batch_log:
        walls = [b["t_finish"] - b["t_dispatch"] for b in r.batch_log
                 if "t_finish" in b]
        if walls:
            report["batch_wall_ms_mean"] = 1e3 * sum(walls) / len(walls)
        report["batches"] = len(r.batch_log)
        h2d = [b.get("h2d_bytes") for b in r.batch_log]
        if None not in h2d:
            report["h2d_bytes_min_max"] = [min(h2d), max(h2d)]
            snap = 2 * cfg["vocab_size"] * cfg["num_topics"] * 4 \
                + cfg["vocab_size"] * 4
            report["h2d_rest_bytes_min_max"] = [min(h2d) - snap,
                                                max(h2d) - snap]
    if args.trace:
        if "ctx" in seen:
            c = seen["ctx"]
            tr, planes, lo, hi = c.trace, c.planes, c.lo, c.hi
        else:                      # a rehearsal keeps its trace on disk
            tr = xplane.load(xplane.newest_file(seen["trace_dir"]))
            lo, hi = tr.spans("bench.window")[0]
            planes = []
        if cfg["kind"] == "serve":
            report["spans"] = serve_report(tr, planes, lo, hi,
                                           r.counts["batches"])
        else:
            report["spans"] = train_report(tr, planes, lo, hi,
                                           r.counts["iterations"])
    out = os.path.join(common.ROOT, ".bench_traces", "reports")
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, f"{args.workload}-{args.seed}-t{args.trace}"
                        ".json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1, default=str)
    common.log(f"span report: {path}")


if __name__ == "__main__":
    main()
