#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <name> --rehearse      # CPU, tiny

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration ``bench/configs/<config>.json`` (its ``kind`` picks the
runner ``bench/harness/<kind>.py``), the traffic mix
``bench/traffic/<traffic>.json``, one reader per metric in
``bench/metrics/<metric>.py`` and one module per correctness check in
``bench/checks/<check>.py``.

The run builds its inputs from ``--seed``, warms up (set-up), measures
for ``--seconds``, then checks what the timed path produced against a
plain reference (``bench/reference``).  With ``--trace 1`` the window is
traced and the per-layer metrics are read from the trace instead of the
end-to-end ones.  The last line of standard output is one JSON object;
the numbers compared, each beside its limit, close standard error and
the result line.  Without a TPU (or with fewer chips than the cell asks
for) the run exits non-zero and prints no result.  ``--rehearse`` runs
the same code on the CPU at the configuration's rehearsal sizes, with
four faked devices, and prints no result line."""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import common  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, rehearsal sizes; prints no result line")
    return ap.parse_args(argv)


def load_cell(name: str):
    """``(benchmark, cell, config, traffic mix)`` for workload ``name``."""
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfg = common.load_json("configs", cell["config"] + ".json")
    mix = common.load_json("traffic", cell["traffic"] + ".json")
    return bench, cell, cfg, mix


def rehearsal(cfg: dict, mix: dict):
    """The configuration and mix with their ``rehearse`` overrides."""
    return (dict(cfg, **cfg.get("rehearse", {})),
            dict(mix, **mix.get("rehearse", {})))


def metrics_for(bench: dict, cell: dict, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def per_layer_context(run, trace_dir, cell_chips):
    from harness import readers, xplane
    tr = xplane.load(xplane.newest_file(trace_dir))
    spans = tr.spans("bench.window")
    if not spans:
        raise SystemExit("the trace holds no bench.window span")
    lo, hi = spans[0]
    kind = run.device["kind"]
    peaks = common.load_json("peaks.json")["devices"]
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    planes = tr.devices()[:cell_chips]
    if not planes:
        raise SystemExit("the trace holds no TPU device plane")
    return readers.Context(trace=tr, planes=planes, lo=lo, hi=hi,
                           peaks=peaks[kind], counts=run.counts,
                           batch_log=run.batch_log)


def execute(args, jax):
    """Run the cell; returns the result dict and the runner's notes."""
    import importlib
    bench, cell, cfg, mix = load_cell(args.workload)
    if args.rehearse:
        cfg, mix = rehearsal(cfg, mix)
    seconds = (args.seconds if args.seconds is not None
               else bench["run_seconds"])
    runner = importlib.import_module(f"harness.{cfg['kind']}")
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(common.ROOT, ".bench_traces", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = runner.run(jax, cfg, mix, args.seed, seconds, cell["chips"],
                     trace_dir=trace_dir)

    metrics, breakdown = {}, None
    if args.trace and args.rehearse:
        common.log("rehearsal: a CPU trace has no TPU plane to read")
    elif args.trace:
        ctx = per_layer_context(run, trace_dir, cell["chips"])
        for m in metrics_for(bench, cell, trace=True):
            val = common.load_module("metrics", m["name"]).read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
        from harness import readers
        breakdown = readers.breakdown(ctx)
        run.device["busy_s"] = ctx.busy_s()
        run.device["window_s"] = ctx.window_s
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in metrics_for(bench, cell, trace=False):
            val = common.load_module("metrics", m["name"]).read(run)
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}

    checks = []
    for name in cfg["checks"]:
        checks += common.load_module("checks", name).check(run.check_input)
    correct = all(val <= limit for _, val, limit in checks)
    for name, val in run.notes.items():
        common.log(f"note {name} {val}")
    for m, v in metrics.items():
        common.log(f"metric {m} {v['value']} {v['unit']}")
    common.log(f"device {json.dumps(run.device)}")
    for name, val, limit in checks:
        common.log(f"check {name} {val} limit {limit}")
    result = {"correct": bool(correct), "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics,
              "device": run.device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": val, "limit": limit}
                        for name, val, limit in checks}
    return result, run.notes


def main(argv=None) -> int:
    args = parse_args(argv)
    _, cell, _, _ = load_cell(args.workload)
    jax = common.start_jax(args.rehearse, cell["chips"])
    result, notes = execute(args, jax)
    if args.rehearse:
        common.log(f"rehearsal done (CPU; no result line): correct="
                   f"{result['correct']}")
        return 0
    print(f"peak HBM {result['device']['memory_peak_bytes']} B, sampler "
          f"{notes['sampler']}", flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
