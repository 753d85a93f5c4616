"""Serving-scheduler driver: replay traffic against a live, hot-swapping
topic-inference service (DESIGN.md §14).

    # serve a snapshot, replay a seeded trace, hot-swap mid-replay
    PYTHONPATH=src python -m repro.launch.lda_serve \
        --snapshot /tmp/a.npz --swap-snapshot /tmp/b.npz --swap-after 16 \
        --requests 64 --rate 200 --sampler scan

    # sharded (out-of-core) snapshots: only the rows the trace touches
    PYTHONPATH=src python -m repro.launch.lda_serve \
        --snapshot-dir /tmp/snapA --swap-snapshot-dir /tmp/snapB \
        --swap-after 16 --requests 64

    # watch a directory: pick up each new snapshot the trainer publishes
    PYTHONPATH=src python -m repro.launch.lda_serve \
        --snapshot /tmp/live/snap_0001.npz --watch /tmp/live --requests 512

Stands up a :class:`ServingScheduler` under wall time, replays a seeded
open-loop Poisson trace (`serve/traffic.py`), and reports served/s,
p50/p99 latency, cache hit rate, and the per-epoch response counts.
Exits non-zero if any admitted request went unanswered or p99 is not
finite — the CI smoke contract (`scripts/ci.sh` pass 8).

The hot-swap is the production loop in miniature: training publishes
snapshot after snapshot, the server flips pointers without dropping a
request (frozen-model serving makes the swap trivial — no KV caches to
migrate, no in-flight state to reconcile; DESIGN.md §14).  ``--swap-*``
drives one deterministic mid-replay swap for CI; ``--watch`` polls a
directory each tick and swaps whenever a newer ``.npz`` appears.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from repro.core import faults
from repro.core.infer import (load_sharded_snapshot_meta, load_snapshot,
                              load_snapshot_rows)
from repro.data import integrity
from repro.launch.compile_cache import use_compile_cache
from repro.launch.samplers import (infer_sampler_choices,
                                   resolve_sampler_choice)
from repro.serve.scheduler import ServingScheduler, WallClock
from repro.serve.traffic import poisson_trace, replay_open_loop


def _load_sharded_pair(args, trace):
    """Row-restricted views for the trace's word set.  BOTH directories
    are restricted with the SAME flat word array, so ``np.unique`` yields
    the same remap — the remapped trace is valid against either view and
    the swap stays a pointer flip."""
    lens = [len(t.tokens) for t in trace]
    flat = np.concatenate([t.tokens for t in trace])
    snap, remapped = load_snapshot_rows(args.snapshot_dir, flat)
    parts = np.split(remapped, np.cumsum(lens)[:-1])
    for t, part in zip(trace, parts):
        t.tokens = part.astype(np.int32)
    swap = None
    if args.swap_snapshot_dir:
        swap, _ = load_snapshot_rows(args.swap_snapshot_dir, flat)
    return snap, swap, flat


def _make_watcher(args, sched, flat=None):
    """Poll ``--watch`` for a snapshot newer than the one being served;
    load + hot-swap when one appears.  Throttled by the scheduler's own
    clock, so the poll cadence needs no extra timer.

    Tolerant of the trainer mid-export (§15): a candidate that fails
    integrity validation — torn ``.npz``, sharded directory whose
    ``meta.json`` hasn't landed yet (it is written LAST, atomically), a
    block file without a matching checksum — is SKIPPED this poll and
    retried on the next, without touching the serving loop or the poll
    watermark.  Only a fully-validated candidate is swapped in.

    ``flat`` is the trace's flat word array when serving row-restricted
    sharded snapshots (the candidate must be restricted with the SAME
    words so the remap matches the in-flight trace)."""
    base = args.snapshot or getattr(args, "snapshot_dir", "")
    state = {"mtime": (os.path.getmtime(base)
                       if base and os.path.exists(base) else 0.0),
             "path": os.path.abspath(base or ""),
             "last_poll": float("-inf")}
    sharded = bool(getattr(args, "snapshot_dir", ""))

    def candidates():
        """(path, mtime) of every plausible candidate under --watch:
        ``.npz`` files, or (sharded mode) subdirectories stamped by
        their ``meta.json`` publish time."""
        try:
            entries = list(os.scandir(args.watch))
        except OSError:
            return
        for e in entries:
            if sharded:
                meta = os.path.join(e.path, "meta.json")
                if e.is_dir() and os.path.exists(meta):
                    yield e.path, os.path.getmtime(meta)
            elif e.name.endswith(".npz"):
                yield e.path, e.stat().st_mtime

    def load_validated(path):
        if sharded:
            integrity.validate_tree(path)
            snap, _ = load_snapshot_rows(
                path, flat if flat is not None else np.zeros(0, np.int32))
            return snap
        return load_snapshot(path)

    def on_tick(sched_, now):
        if now - state["last_poll"] < args.watch_interval:
            return
        state["last_poll"] = now
        newest, newest_m = None, state["mtime"]
        for path, m in candidates():
            if m > newest_m and os.path.abspath(path) != state["path"]:
                newest, newest_m = path, m
        if newest is None:
            return
        try:
            epoch = sched_.swap_snapshot(load_validated(newest))
        except (integrity.IntegrityError, ValueError, OSError) as e:
            # partial or corrupt export: keep serving the old epoch and
            # leave the watermark alone so the next poll retries
            print(f"  [watch] skipped {newest}: {type(e).__name__}: {e}")
            return
        state["mtime"], state["path"] = newest_m, os.path.abspath(newest)
        print(f"  [watch] swapped to {newest} (epoch {epoch})")

    return on_tick


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--snapshot", default="",
                    help="frozen snapshot .npz (lda_train --snapshot-out)")
    ap.add_argument("--snapshot-dir", default="",
                    help="sharded snapshot directory (lda_train "
                         "--snapshot-dir); rows are loaded restricted to "
                         "the trace's word set (DESIGN.md §13)")
    ap.add_argument("--swap-snapshot", default="",
                    help="second .npz to hot-swap to mid-replay")
    ap.add_argument("--swap-snapshot-dir", default="",
                    help="second sharded snapshot directory to hot-swap to")
    ap.add_argument("--swap-after", type=int, default=-1,
                    help="hot-swap immediately before the Nth submission "
                         "(default: midpoint when a swap target is given)")
    ap.add_argument("--watch", default="",
                    help="directory to poll for newer snapshots (.npz, "
                         "or sharded directories with --snapshot-dir); "
                         "each validated new one is hot-swapped in live — "
                         "partial/corrupt exports are skipped and retried")
    ap.add_argument("--watch-interval", type=float, default=0.2,
                    help="seconds between --watch polls")
    ap.add_argument("--sampler", choices=infer_sampler_choices(),
                    default="scan")
    ap.add_argument("--force", action="store_true",
                    help="run an explicitly requested *_pallas sampler "
                         "in interpret mode off-TPU instead of refusing")
    ap.add_argument("--sweeps", type=int, default=5)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rate", type=float, default=100.0,
                    help="offered load, queries/s (Poisson arrivals)")
    ap.add_argument("--max-len", type=int, default=48,
                    help="doc-length clip of the heavy-tailed trace")
    ap.add_argument("--hot-fraction", type=float, default=0.25,
                    help="fraction of requests drawn from the hot pool "
                         "(exercises the multiset cache)")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-queue", type=int, default=1024)
    ap.add_argument("--batch-delay", type=float, default=0.0,
                    help="hold a partial batch at most this long (s)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--breaker-threshold", type=int, default=3,
                    help="consecutive replica failures that open its "
                         "circuit breaker (DESIGN.md §15)")
    ap.add_argument("--breaker-cooldown", type=float, default=0.25,
                    help="seconds an open breaker waits before a "
                         "half-open probe")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="per-request retry budget across replicas")
    ap.add_argument("--request-deadline", type=float, default=None,
                    help="reject (structured) any admitted request "
                         "queued longer than this many seconds")
    ap.add_argument("--inject-replica-fail", type=int, default=-1,
                    metavar="R",
                    help="fault injection: replica R raises on every "
                         "dispatch — the degraded-mode smoke (breaker "
                         "opens, retries answer on the others)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    use_compile_cache()

    if bool(args.snapshot) == bool(args.snapshot_dir):
        ap.error("exactly one of --snapshot / --snapshot-dir is required")
    if args.swap_snapshot and args.swap_snapshot_dir:
        ap.error("--swap-snapshot and --swap-snapshot-dir are mutually "
                 "exclusive")
    if args.swap_snapshot_dir and not args.snapshot_dir:
        ap.error("--swap-snapshot-dir needs --snapshot-dir (the row "
                 "restriction must share one word set)")
    if args.watch and not (args.snapshot or args.snapshot_dir):
        ap.error("--watch needs --snapshot (.npz mode) or "
                 "--snapshot-dir (sharded mode)")

    if args.snapshot_dir:
        vocab = load_sharded_snapshot_meta(args.snapshot_dir)["vocab_size"]
    else:
        snap = load_snapshot(args.snapshot)
        vocab = snap.vocab_size
    trace = poisson_trace(args.requests, args.rate, vocab, seed=args.seed,
                          max_len=args.max_len,
                          hot_fraction=args.hot_fraction)
    swap_snap, flat = None, None
    if args.snapshot_dir:
        snap, swap_snap, flat = _load_sharded_pair(args, trace)
    elif args.swap_snapshot:
        swap_snap = load_snapshot(args.swap_snapshot)
    swap_after = None
    if swap_snap is not None:
        swap_after = (args.swap_after if args.swap_after >= 0
                      else args.requests // 2)

    args.sampler = resolve_sampler_choice(
        args.sampler, force=args.force, num_topics=snap.num_topics,
        max_doc_len=args.max_len)
    print(f"serving V={snap.vocab_size:,} K={snap.num_topics} "
          f"fp={snap.fingerprint()} sampler={args.sampler} "
          f"replicas={args.replicas} max_batch={args.max_batch}")

    plan = None
    if args.inject_replica_fail >= 0:
        if args.inject_replica_fail >= args.replicas:
            ap.error(f"--inject-replica-fail {args.inject_replica_fail} "
                     f"is out of range for --replicas {args.replicas}")
        plan = faults.FaultPlan.replica_fail(args.inject_replica_fail,
                                             nth=0, seed=args.seed)
        print(f"fault injection: replica {args.inject_replica_fail} "
              "fails every dispatch")
    sched = ServingScheduler(
        snap, sampler=args.sampler, num_sweeps=args.sweeps, seed=args.seed,
        num_replicas=args.replicas, max_queue=args.max_queue,
        max_batch=args.max_batch, max_batch_delay=args.batch_delay,
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown=args.breaker_cooldown,
        max_retries=args.max_retries,
        request_deadline=args.request_deadline,
        fault_plan=plan, clock=WallClock())
    buckets = sched.warm(args.max_len)   # compile outside the replay
    print(f"warmed {buckets} (batch, token) buckets")
    on_tick = _make_watcher(args, sched, flat=flat) if args.watch else None
    summary = replay_open_loop(sched, trace, swap_after=swap_after,
                               swap_snapshot=swap_snap, on_tick=on_tick)

    print(f"replayed {summary['requests']} requests in "
          f"{summary['elapsed_s']:.2f}s: {summary['served_qps']:,.1f} "
          f"served/s (offered {summary['offered_qps']:,.1f}/s)")
    print(f"latency p50 {summary['p50_ms']:.2f} ms  "
          f"p99 {summary['p99_ms']:.2f} ms; cache "
          f"{summary['cache']['hits']}/{summary['cache']['hits'] + summary['cache']['misses']} hit; "
          f"rejections {summary['rejections'] or 'none'}")
    print(f"epochs served: {summary['epochs']} over "
          f"{sched.swaps} swap(s); dropped {summary['dropped']}")
    log = list(sched.batch_log)
    if log:
        h2d_mb = sum(b["h2d_bytes"] for b in log) / len(log) / 1e6
        wait_ms = (1e3 * sum(b["wait_s"] for b in log)
                   / sum(b["size"] for b in log))
        print(f"host path: {h2d_mb:,.4f} MB host->device per batch, "
              f"queue wait {wait_ms:.2f} ms per request "
              f"(last {len(log)} batches)")
    st = sched.stats()
    print(f"faults: {st['faults']}")
    print("breakers: " + "  ".join(
        f"replica {i}: {h['state']} ({h['successes']} ok / "
        f"{h['failures']} fail, {h['opens']} open(s))"
        for i, h in enumerate(st["replicas"])))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({k: v for k, v in summary.items()}, f, indent=1,
                      default=str)
    if summary["dropped"] != 0:
        sys.exit(f"{summary['dropped']} admitted requests went "
                 "unanswered — serving smoke FAILED")
    if summary["served"] and not np.isfinite(summary["p99_ms"]):
        sys.exit("non-finite p99 latency — serving smoke FAILED")
    if swap_after is not None and len(summary["epochs"]) < 2:
        sys.exit("hot-swap never served the new epoch — smoke FAILED")


if __name__ == "__main__":
    main()
