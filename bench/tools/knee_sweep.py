#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest open-loop rate the system
sustains.  One process builds the cell's model and scheduler once, then
replays the cell's mix at each rate for ``--seconds``.

    python3 bench/tools/knee_sweep.py --workload serve-pubmed-k1000-steady \
        --rates 20,30,40,50,60,70,80 --seconds 10 --seed 1

A rate is sustained when the window completes at least 97% of what it
offers and the 95th percentile stays under ``--p95-limit-ms``."""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from harness import common, gen, serve  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--p95-limit-ms", type=float, default=1000.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.argv = sys.argv[:1]
    import run as bench_run
    _, cell, cfg, mix = bench_run.load_cell(args.workload)
    if args.rehearse:
        cfg, mix = bench_run.rehearsal(cfg, mix)
    jax = common.start_jax(args.rehearse, cell["chips"])
    rates = [float(r) for r in args.rates.split(",")]
    traces = [gen.requests(args.seed + 1 + i, args.seconds,
                           cfg["vocab_size"], dict(mix, rate_qps=rate))
              for i, rate in enumerate(rates)]
    _, _, sched = serve.build(jax, cfg, args.seed,
                              max(map(serve.max_len, traces)),
                              max_queue=10 ** 6)
    rows, knee = [], None
    for rate, trace in zip(rates, traces):
        before = len(sched.results)
        ids, t0 = serve.replay(sched, trace, args.seconds)
        end = t0 + args.seconds
        sched.clock.sleep(end - sched.clock.now())
        backlog = sched.pending
        sched.drain()
        res = [sched.results[r] for r in ids]
        lat = np.asarray([r.latency for r in res if r.status == "ok"])
        done = sum(r.status == "ok" and r.t_finish <= end for r in res)
        row = {"rate_qps": rate, "offered": len(trace),
               "completed_in_window_qps": done / args.seconds,
               "p50_ms": float(np.percentile(lat, 50) * 1e3),
               "p95_ms": float(np.percentile(lat, 95) * 1e3),
               "backlog_at_close": backlog,
               "answers": len(sched.results) - before}
        ok = (done >= 0.97 * len(trace)
              and row["p95_ms"] <= args.p95_limit_ms)
        row["sustained"] = bool(ok)
        if ok:
            knee = rate
        rows.append(row)
        common.log(json.dumps(row))
    print(json.dumps({"workload": args.workload, "knee_qps": knee,
                      "rows": rows}))


if __name__ == "__main__":
    main()
