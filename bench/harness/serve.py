"""Serving cells: a ``ServingScheduler`` over a frozen model whose counts
are made from the seed, fed an open-loop trace for ``seconds``.

The replay loop is copied from ``repro.serve.traffic.replay_open_loop``:
each pass submits every request whose scheduled time has come, stamped
with that time (so queueing lands in latency), ticks the scheduler, and
otherwise sleeps to the next arrival.  The window closes at ``seconds``;
then the queue is drained outside the window, so every request of the
window gets its answer and its latency."""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from harness import common, gen
from harness.run_record import Run

# latency given to a refused or failed request: above any limit
MISSED_S = 3600.0
IDLE_STEP_S = 1e-3


@dataclasses.dataclass
class ServeOutcome:
    """The inputs of the serving checks."""
    ckt: np.ndarray            # the model's counts, as the benchmark made them
    ck: np.ndarray
    alpha: float
    beta: float
    seed: int
    sweeps: int
    tokens: List[np.ndarray]   # every request of the window
    answers: List[Optional[np.ndarray]]   # its θ, or None when refused


def replay(sched, trace, seconds: float):
    """Open-loop replay for ``seconds``; returns ``(request ids, t0)``."""
    clock = sched.clock
    t0 = clock.now()
    ids, i = [], 0
    with common.span("bench.replay"):
        while True:
            now = clock.now() - t0
            if now >= seconds:
                break
            while i < len(trace) and trace[i][0] <= now:
                with common.span("bench.submit"):
                    ids.append(sched.submit(trace[i][1],
                                            now=t0 + trace[i][0]))
                i += 1
            with common.span("bench.tick"):
                ticked = sched.tick()
            if sched.pending and not ticked:
                clock.sleep(IDLE_STEP_S)
            elif not sched.pending:
                nxt = trace[i][0] if i < len(trace) else seconds
                clock.sleep(max(min(nxt, seconds) - (clock.now() - t0),
                                IDLE_STEP_S))
    return ids, t0


def max_len(trace) -> int:
    """The longest query of a trace: the warm-up's reach."""
    return max(toks.size for _, toks in trace)


def build(jax, cfg: dict, seed: int, max_len: int, max_queue: int):
    """The frozen model from the seed and a warmed scheduler over it."""
    from repro.core.infer import ModelSnapshot
    from repro.serve.scheduler import ServingScheduler, WallClock
    m = cfg
    ckt = gen.model_counts(jax, seed, m["vocab_size"], m["num_topics"],
                           cfg["counts_law"])
    ck = ckt.sum(axis=0, dtype=np.int64).astype(np.int32)
    snap = ModelSnapshot.from_counts(ckt, ck, m["alpha"], m["beta"])
    sched = ServingScheduler(
        snap, sampler=cfg["sampler"], num_sweeps=cfg["sweeps"], seed=seed,
        num_replicas=cfg["replicas"], max_queue=max_queue,
        max_batch=cfg["max_batch"], clock=WallClock())
    # every (batch, token) bucket the traffic can reach, and no others
    buckets = sched.warm(max_len)
    common.log(f"serve: V={m['vocab_size']:,} K={m['num_topics']:,} "
               f"tokens={int(ck.sum()):,} sampler={cfg['sampler']} "
               f"replicas={cfg['replicas']} max_batch={cfg['max_batch']} "
               f"max_queue={max_queue} warmed {buckets} buckets")
    return ckt, ck, sched


def run(jax, cfg: dict, mix: dict, seed: int, seconds: float,
        chips: int, trace_dir=None) -> Run:
    m = cfg
    with common.span("bench.setup"):
        trace = gen.requests(seed, seconds, m["vocab_size"], mix)
        ckt, ck, sched = build(jax, cfg, seed, max_len(trace),
                               cfg["max_queue"])
    setup_s = common.process_age_s()

    if trace_dir:
        common.start_trace(jax, trace_dir)
    with common.span("bench.window"):
        ids, t0 = replay(sched, trace, seconds)
        window_end = t0 + seconds
        sched.clock.sleep(window_end - sched.clock.now())
    if trace_dir:
        jax.profiler.stop_trace()
    batch_log = [b for b in sched.batch_log if b["t_dispatch"] < window_end]
    sched.drain()
    device = common.device_info(jax, chips)

    lat, answers, done_in_window, failed = [], [], 0, 0
    for rid in ids:
        r = sched.results.get(rid)
        if r is None or r.status != "ok":
            failed += 1
            lat.append(MISSED_S)
            answers.append(None)
            continue
        lat.append(r.latency)
        answers.append(np.asarray(r.theta))
        done_in_window += r.t_finish <= window_end
    outcome = ServeOutcome(ckt=ckt, ck=ck, alpha=m["alpha"], beta=m["beta"],
                           seed=seed, sweeps=cfg["sweeps"],
                           tokens=[t for _, t in trace[:len(ids)]],
                           answers=answers)
    st = sched.stats()
    common.log(f"serve: {len(ids)} submitted, {len(ids) - failed} "
               f"answered, {done_in_window} inside the window, "
               f"{st['batches']} batches, cache hits {st['cache']['hits']}, "
               f"rejections {st['rejections'] or 'none'}")
    del sched
    return Run(setup_s=setup_s, window_s=seconds, chips=chips,
               counts={"requests": len(ids),
                       "batches": len(batch_log),
                       "max_batch": cfg["max_batch"]},
               attempted=len(ids), failed=failed, device=device,
               check_input=outcome, latencies_s=lat, batch_log=batch_log,
               notes={"sampler": cfg["sampler"]})
