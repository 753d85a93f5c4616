"""Seeded serving traffic: Poisson arrivals, heavy-tailed doc lengths,
and the open-loop replay loop (DESIGN.md §14).

The same trace + replay machinery drives two consumers: the
deterministic virtual-clock tests (`tests/test_scheduler.py`) and the
``lda_serve`` CLI.  A trace is a pure function of its seed, so replaying
it twice — even across processes — submits bit-identical requests at
identical scheduled times.

**Open loop.**  Arrivals follow the SCHEDULE, not the server: a request
whose scheduled time has passed while the server was busy is submitted
late but stamped with its scheduled arrival, so queueing delay lands in
measured latency.  Closed-loop benches (back-to-back batches) hide
exactly this — the latency a user actually sees when the system
saturates.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import numpy as np

from repro.serve.scheduler import ServingScheduler


@dataclasses.dataclass
class TraceRequest:
    t: float                 # scheduled arrival, seconds from replay start
    tokens: np.ndarray       # int32 word ids


def poisson_trace(num_requests: int, rate_qps: float, vocab_size: int, *,
                  seed: int = 0, len_tail: float = 1.3, min_len: int = 4,
                  max_len: int = 64, hot_fraction: float = 0.0,
                  hot_pool: int = 8) -> List[TraceRequest]:
    """Synthetic serving trace: exponential inter-arrival gaps (Poisson
    process at ``rate_qps``) and heavy-tailed doc lengths (``min_len - 1
    + Zipf(len_tail)``, clipped to ``max_len`` — most queries are short,
    a few are near the clip, the length mix real query traffic shows).

    ``hot_fraction`` of requests repeat one of ``hot_pool`` fixed hot
    documents (by EXACT token multiset), modelling repeated/trending
    queries — the traffic the scheduler's multiset cache exists for.
    Everything is drawn from one seeded generator: same seed, same
    trace, bit for bit."""
    if rate_qps <= 0:
        raise ValueError(f"rate_qps must be positive, got {rate_qps}")
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(1.0 / rate_qps, size=num_requests))
    hot = [rng.integers(0, vocab_size, size=int(np.clip(
               min_len - 1 + rng.zipf(len_tail), min_len, max_len))
           ).astype(np.int32) for _ in range(max(hot_pool, 1))]
    trace = []
    for i in range(num_requests):
        if hot_fraction > 0 and rng.random() < hot_fraction:
            tokens = hot[int(rng.integers(0, len(hot)))]
        else:
            n = int(np.clip(min_len - 1 + rng.zipf(len_tail),
                            min_len, max_len))
            tokens = rng.integers(0, vocab_size, size=n).astype(np.int32)
        trace.append(TraceRequest(float(t[i]), tokens))
    return trace


def replay_open_loop(sched: ServingScheduler,
                     trace: Sequence[TraceRequest], *,
                     swap_after: Optional[int] = None,
                     swap_snapshot=None,
                     on_tick: Optional[Callable] = None,
                     idle_step: float = 1e-3) -> dict:
    """Replay a trace through a scheduler under ITS clock and drain it.

    Each loop iteration submits every request whose scheduled time has
    arrived (stamped with the scheduled time — open loop), ticks the
    scheduler, and otherwise sleeps the clock forward: to the next
    arrival when idle, by ``idle_step`` when a partial batch is being
    held for ``max_batch_delay``.  Under a `VirtualClock` the whole
    replay is deterministic and instant; under a `WallClock` it is the
    real serving loop.

    ``swap_after=N`` hot-swaps to ``swap_snapshot`` immediately before
    the N-th submission — the mid-replay swap the hot-swap tests and the
    CI smoke drive.  ``on_tick(sched, now)`` runs once per loop (the
    ``lda_serve --watch`` hook).  Returns a summary dict; after it, every
    admitted request has a response (asserted via ``sched.dropped()``).
    """
    t0 = sched.clock.now()
    i = 0
    swap_epoch = None
    while i < len(trace) or sched.pending:
        now = sched.clock.now() - t0
        while i < len(trace) and trace[i].t <= now:
            if swap_after is not None and swap_snapshot is not None \
                    and i == swap_after:
                swap_epoch = sched.swap_snapshot(swap_snapshot)
            sched.submit(trace[i].tokens, now=t0 + trace[i].t)
            i += 1
        ticked = sched.tick()
        if on_tick is not None:
            on_tick(sched, now)
        if sched.pending and not ticked:
            # a partial batch is ageing toward its deadline
            sched.clock.sleep(idle_step)
        elif not sched.pending and i < len(trace):
            # idle: jump to the next scheduled arrival
            sched.clock.sleep(max(trace[i].t - (sched.clock.now() - t0),
                                  idle_step))
    sched.drain()
    elapsed = sched.clock.now() - t0
    epochs: dict = {}
    for r in sched.ok_responses():
        epochs[r.epoch] = epochs.get(r.epoch, 0) + 1
    return {
        "requests": len(trace),
        "elapsed_s": float(elapsed),
        "offered_qps": (len(trace) / trace[-1].t if len(trace)
                        and trace[-1].t > 0 else float("nan")),
        "served_qps": (sched.served / elapsed if elapsed > 0
                       else float("nan")),
        "dropped": sched.dropped(),
        "swap_epoch": swap_epoch,
        "epochs": epochs,
        **sched.latency_summary(),
        **{k: v for k, v in sched.stats().items()
           if k in ("admitted", "rejections", "cache", "swaps", "batches",
                    "faults")},
    }
