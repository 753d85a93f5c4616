"""Exact up to float32 rounding: every answer the window served is one
that the fold-in draw rule gives from the request's own draws
(``reference/lda.py``).  The reference derives the draws from the seed
contract and follows each draw in float64 and, where the uniform lies
within float32 rounding of a boundary, both sides.

Two passes: a cheap one that branches only within the rounding that
float32 sums reach in practice (``spread="typical"``) and, for an answer
it does not explain, one that branches within the rounding of any
summation order (``"any_order"``).  An answer outside the second pass's
complete set is unexplained; one that neither pass explains because the
second grew past ``PATH_CAP`` paths is unverified.  Covers the
scheduler, ``TopicInferenceServer`` and the fold-in sampler.

Answers are judged in ``WORKERS`` processes at once, after the window:
a query of 90 tokens takes a fifth of a second or more to replay."""
import functools
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from harness import common
from reference import lda as ref

LIMITS = {"unexplained_answers": 0, "unverified_share": 20.0}
TYPICAL_CAP = 1024
PATH_CAP = 256
# processes that judge answers at once, and answers handed to each at a time
WORKERS = 12
CHUNK = 8


def check(o):
    k = o.ckt.shape[1]
    v = o.ckt.shape[0]
    alpha = np.full(k, o.alpha)
    fp = ref.model_fingerprint(o.ckt, o.ck, np.full(k, o.alpha, np.float32),
                               o.beta)
    items = []
    for toks, th in zip(o.tokens, o.answers):
        if th is None:
            continue
        canon, z0, u = ref.request_draws(o.seed, fp, toks, k, o.sweeps)
        items.append((o.ckt[canon], z0, u,
                      ref.counts_of_theta(th, canon.size, alpha)))
    chunks = [items[i:i + CHUNK] for i in range(0, len(items), CHUNK)]
    judge = functools.partial(ref.foldin_verdicts, ck=o.ck, alpha=alpha,
                              beta=o.beta, vbeta=o.beta * v,
                              typical_cap=TYPICAL_CAP, path_cap=PATH_CAP)
    tally = {"explained": 0, "unexplained": 0, "unverified": 0}
    workers = max(1, min(WORKERS, os.cpu_count() or 1, len(chunks)))
    # spawned, not forked: the parent holds the accelerator's runtime
    with ProcessPoolExecutor(workers, mp_context=multiprocessing.get_context(
            "spawn")) as pool:
        for verdicts in pool.map(judge, chunks):
            for verdict in verdicts:
                tally[verdict] += 1
    checked = sum(tally.values())
    common.log(f"check foldin_replay: {checked} answers checked: {tally}")
    return [("unexplained_answers", float(tally["unexplained"]),
             LIMITS["unexplained_answers"]),
            ("unverified_share",
             100.0 * tally["unverified"] / max(checked, 1),
             LIMITS["unverified_share"])]
