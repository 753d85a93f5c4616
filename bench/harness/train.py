"""Training cells: one ``ModelParallelLDA`` built from the seed, warmed by
whole iterations through the same ``step`` the window drives, then timed
over whole iterations.

The window runs iterations back to back, each ended by
``block_until_ready``; it runs at least one and starts none that the
previous one's time says would end after ``seconds``.  Before each
iteration the device keeps a copy of the assignments, so the checks see
the last iteration's start and end state."""
from __future__ import annotations

import dataclasses
import time
import types

import numpy as np

from harness import common, gen
from harness.run_record import Run


@dataclasses.dataclass
class TrainOutcome:
    """The inputs of the training checks."""
    word: np.ndarray
    doc: np.ndarray
    vocab_size: int
    num_docs: int
    num_topics: int
    alpha: float
    beta: float
    cycles: int
    seed: int
    z_before: np.ndarray       # assignments at the last iteration's start
    z_after: np.ndarray        # and at its end
    ckt: np.ndarray            # the program's counts at the end
    cdk: np.ndarray
    ck: np.ndarray
    ck_synced: np.ndarray
    ck_local: np.ndarray


def run(jax, cfg: dict, mix: dict, seed: int, seconds: float,
        chips: int, trace_dir=None) -> Run:
    import jax.numpy as jnp

    from repro.core.engine import ModelParallelLDA
    from repro.core.engine import state as engine_state
    from repro.data.corpus import Corpus

    m = cfg
    v, k, d = m["vocab_size"], m["num_topics"], m["num_docs"]
    with common.span("bench.setup"):
        doc, word = gen.corpus(seed, d, v, mix)
        lda = ModelParallelLDA(
            Corpus(doc, word, d, v), k, num_workers=cfg["num_workers"],
            alpha=m["alpha"], beta=m["beta"], seed=seed,
            sampler_mode=cfg["sampler"], backend=cfg["backend"],
            blocks_per_worker=cfg["blocks_per_worker"],
            table_lifetime=cfg["table_lifetime"], track_error=False)
        for _ in range(cfg["warm_iterations"]):
            jnp.copy(lda.state.z).block_until_ready()
            lda.step()
            jax.block_until_ready(lda.state)
    common.log(f"train: D={d:,} N={word.shape[0]:,} V={v:,} K={k:,} "
               f"sampler={lda.sampler_mode} backend={lda.backend} "
               f"M={lda.num_workers} S={lda.blocks_per_worker} "
               f"table_lifetime={lda.table_lifetime}")
    setup_s = common.process_age_s()

    if trace_dir:
        common.start_trace(jax, trace_dir)
    n_iter, last = 0, 0.0
    with common.span("bench.window"):
        t0 = time.perf_counter()
        while True:
            z_prev = jnp.copy(lda.state.z)
            t_it = time.perf_counter()
            with common.span("bench.step"):
                lda.step()
                jax.block_until_ready(lda.state)
            last = time.perf_counter() - t_it
            n_iter += 1
            if time.perf_counter() - t0 + last > seconds:
                break
        window_s = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
    device = common.device_info(jax, chips)

    counts = lda.gather_counts()
    outcome = TrainOutcome(
        word=word, doc=doc, vocab_size=v, num_docs=d, num_topics=k,
        alpha=m["alpha"], beta=m["beta"], cycles=m["mh_cycles"],
        seed=seed,
        z_before=engine_state.gather_assignments(
            lda.layout, types.SimpleNamespace(z=z_prev)),
        z_after=lda.assignments(),
        ckt=np.asarray(counts.ckt), cdk=np.asarray(counts.cdk),
        ck=np.asarray(counts.ck),
        ck_synced=np.asarray(lda.state.ck_synced),
        ck_local=np.asarray(lda.state.ck_local))
    sampler = lda.sampler_mode
    del lda, z_prev, counts
    return Run(setup_s=setup_s, window_s=window_s, chips=chips,
               counts={"tokens": n_iter * word.shape[0],
                       "iterations": n_iter},
               attempted=n_iter, failed=0, device=device,
               check_input=outcome,
               notes={"iteration_s": last, "sampler": sampler})
