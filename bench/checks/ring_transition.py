"""Statistical: the topics drawn in the last timed iteration of the ring
follow the Metropolis-Hastings transition of the round each token was
sampled in (``reference/ring.py``), computed exactly from the counts the
ring's schedule gives that round: the round start's as target, the
block's first residency's as word proposal, the iteration start's as doc
proposal.

``SAMPLE`` tokens drawn from the seed, an equal share from each of the
iteration's rounds, give the two standard scores of
``checks/mh_transition.py`` (``stay_z``, ``logp_z``), with its limits.
Covers the rotation, the traveling word tables, the per-round ``C_k``
sync, the MH kernel and the document sharding; at ``M = S = 1`` it is
``mh_transition``."""
import numpy as np

from reference import lda as ref
from reference import ring

SAMPLE = 16384
LIMITS = {"stay_z": 12.0, "logp_z": 12.0}


def check(o):
    m, s, k = o.num_workers, o.blocks_per_worker, o.num_topics
    b = m * s
    word, doc = np.asarray(o.word), np.asarray(o.doc)
    z_before, z_after = np.asarray(o.z_before), np.asarray(o.z_after)
    rounds = ring.token_rounds(word, doc, o.vocab_size, m, s,
                               o.doc_to_worker)
    rng = np.random.default_rng(np.random.SeedSequence([int(o.seed), 2]))
    picks = []
    for r in range(b):
        pool = np.flatnonzero(rounds == r)
        picks.append(np.sort(rng.choice(
            pool, size=min(SAMPLE // b, pool.size), replace=False)))
    idx = np.concatenate(picks)
    counts = ring.round_start_counts(word, doc, z_before, z_after, rounds,
                                     word[idx], doc[idx], k, b)
    word_prop = {}
    ps, lo = [], 0
    for r, (pick, (ckt, cdk, ck)) in enumerate(zip(picks, counts)):
        if r == 0:
            doc_prop = cdk
        if r < s:
            word_prop[r] = ckt
        here = slice(lo, lo + pick.size)
        lo += pick.size
        if pick.size == 0:
            continue
        # a block resident in round r was first resident in round r mod S
        first = ring.first_residency(ring.block_for(0, r, m, s), m)
        ps.append(ring.ring_transition(
            z_before[pick], ckt[here], cdk[here], ck,
            word_prop[first][here], doc_prop[here], o.alpha, o.beta,
            o.beta * o.vocab_size, o.cycles))
    stay_z, logp_z = ref.transition_scores(
        np.concatenate(ps), z_before[idx], z_after[idx])
    return [("stay_z", abs(stay_z), LIMITS["stay_z"]),
            ("logp_z", abs(logp_z), LIMITS["logp_z"])]
