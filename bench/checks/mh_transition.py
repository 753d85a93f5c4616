"""Statistical: the topics drawn in the last timed iteration follow the
Metropolis-Hastings transition that the configuration states
(``reference/lda.py``), computed exactly from the iteration's start state.

A sample of ``SAMPLE`` tokens drawn from the seed gives two standard
scores: of how many tokens kept their topic (``stay_z``), and of the
summed log-probability of the drawn topics (``logp_z``).  Both are near
N(0, 1) for a sound sampler; the limits on their size are set from runs
(PERF.md).  Covers the MH kernel and the proposal tables it draws from."""
import numpy as np

from reference import lda as ref

SAMPLE = 16384
BLOCK = 2048
LIMITS = {"stay_z": 12.0, "logp_z": 12.0}


def check(o):
    n = o.word.shape[0]
    rng = np.random.default_rng(np.random.SeedSequence([int(o.seed), 2]))
    idx = np.sort(rng.choice(n, size=min(SAMPLE, n), replace=False))
    ckt, cdk, ck = ref.recount(o.word, o.doc, o.z_before, o.vocab_size,
                               o.num_docs, o.num_topics)
    s = np.asarray(o.z_before)[idx]
    z_new = np.asarray(o.z_after)[idx]
    p = np.concatenate([
        ref.mh_transition(
            s[b:b + BLOCK], ckt[o.word[idx[b:b + BLOCK]]].astype(np.float64),
            cdk[o.doc[idx[b:b + BLOCK]]].astype(np.float64), ck, o.alpha,
            o.beta, o.beta * o.vocab_size, o.cycles)
        for b in range(0, idx.size, BLOCK)])
    stay_z, logp_z = ref.transition_scores(p, s, z_new)
    return [("stay_z", abs(stay_z), LIMITS["stay_z"]),
            ("logp_z", abs(logp_z), LIMITS["logp_z"])]
