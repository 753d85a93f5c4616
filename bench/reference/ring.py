"""The paper's word-block ring, stated plainly: which block a worker
holds in each round, which worker holds a document, and the exact
Metropolis-Hastings transition of a token sampled in round ``r`` of an
``S·M``-round iteration (arXiv 1411.2305, Algorithm 1, with ``S`` blocks
per worker).

Schedule.  The vocabulary is cut into ``B = S·M`` equal id ranges of
``Vb = ⌈V/B⌉`` words, numbered slot-major: block ``b = s·M + w`` starts
the iteration in slot ``s`` of worker ``w``.  In round ``r`` worker
``m`` holds block ``(r mod S)·M + ((m + ⌊r/S⌋) mod M)``; a block is first
resident in round ``⌊b/M⌋``, at its home worker.  Documents are dealt to
workers round robin by id.

Transition.  A token of block ``b`` at worker ``m`` is sampled in the
round ``r`` in which ``m`` holds ``b``, by ``cycles`` pairs of MH steps
(``lda.mh_transition``'s cycle) in which three sets of counts differ:

* the target ``π`` takes the counts at the round's start: recounted from
  the iteration's start state ``z_before``, with every token sampled in
  an earlier round at ``z_after`` (``C_k`` is agreed at every round's
  end, so it too is the round start's);
* the word proposal takes the block's counts at its first residency,
  when its table is built, and travels with it;
* the doc proposal takes the iteration's start ``C_dk``.

At ``M = S = 1`` all three are the iteration start's and
:func:`ring_transition` is ``lda.mh_transition``."""
from __future__ import annotations

import numpy as np

from reference.lda import SCALE, grid_prior, mh_step


def block_size(vocab_size: int, num_workers: int,
               blocks_per_worker: int) -> int:
    return -(-vocab_size // (num_workers * blocks_per_worker))


def block_for(worker, rnd, num_workers: int, blocks_per_worker: int):
    """The block ``worker`` holds resident in round ``rnd``."""
    m, s = num_workers, blocks_per_worker
    return (rnd % s) * m + (worker + rnd // s) % m


def round_of(worker, block, num_workers: int, blocks_per_worker: int):
    """The round in which ``worker`` holds ``block``: the inverse of
    :func:`block_for` within one iteration."""
    m, s = num_workers, blocks_per_worker
    return ((block % m - worker) % m) * s + block // m


def first_residency(block, num_workers: int):
    """The round in which ``block`` is first resident: its home slot."""
    return block // num_workers


def worker_of_doc(doc, num_workers: int, rule: str = "round_robin"):
    """The worker that holds document ``doc``."""
    if rule != "round_robin":
        raise ValueError(f"unknown rule for dealing documents: {rule!r}")
    return np.asarray(doc) % num_workers


def token_rounds(word, doc, vocab_size: int, num_workers: int,
                 blocks_per_worker: int,
                 rule: str = "round_robin") -> np.ndarray:
    """The round in which each token is sampled."""
    vb = block_size(vocab_size, num_workers, blocks_per_worker)
    return round_of(worker_of_doc(doc, num_workers, rule),
                    np.asarray(word) // vb, num_workers, blocks_per_worker)


def count_rows(keys, z, want, num_topics: int) -> np.ndarray:
    """Counts ``[len(want), K]``, int64: per entry of ``want``, the tokens
    whose key (word or document) it is, by topic ``z``.  Only those
    tokens count, so ``keys`` and ``z`` may hold just them."""
    keys, z = np.asarray(keys), np.asarray(z, np.int64)
    u, inv = np.unique(want, return_inverse=True)
    slot = np.full(max(int(keys.max(initial=0)), int(u[-1])) + 1, -1,
                   np.int64)
    slot[u] = np.arange(u.size)
    pos = slot[keys]
    sel = pos >= 0
    c = np.bincount(pos[sel] * num_topics + z[sel],
                    minlength=u.size * num_topics)
    return c.reshape(u.size, num_topics)[inv]


def round_start_counts(word, doc, z_before, z_after, rounds, words, docs,
                       num_topics: int, num_rounds: int):
    """For each round ``r`` of the iteration in turn, the counts at its
    start: ``(C_wk rows of words, C_dk rows of docs, C_k)``, int64,
    counted from ``z_before`` with every token of a round before ``r`` at
    ``z_after``.  After each round its tokens move from ``z_before`` to
    ``z_after``; only the tokens of ``words`` and ``docs`` give their
    rows."""
    k, rounds = num_topics, np.asarray(rounds)
    parts = []
    for keys, want in ((np.asarray(word), words), (np.asarray(doc), docs)):
        tok = np.flatnonzero(np.isin(keys, want))
        parts.append((keys[tok], z_before[tok], z_after[tok], rounds[tok],
                      want))
    rows = [count_rows(kk, zb, want, k) for kk, zb, _, _, want in parts]
    ck = np.bincount(z_before, minlength=k)
    for r in range(num_rounds):
        yield rows[0], rows[1], ck
        rows = [cur + count_rows(kk[rd == r], za[rd == r], want, k)
                - count_rows(kk[rd == r], zb[rd == r], want, k)
                for cur, (kk, zb, za, rd, want) in zip(rows, parts)]
        moved = rounds == r
        ck = (ck + np.bincount(z_after[moved], minlength=k)
              - np.bincount(z_before[moved], minlength=k))


def ring_transition(s, ckt_rows, cdk_rows, ck, word_prop_rows,
                    doc_prop_rows, alpha, beta, vbeta, cycles: int):
    """Exact topic distribution ``[n, K]`` after the round for tokens with
    round-start topic ``s``: target from the round-start count rows of
    their word and doc and the totals ``ck``; word proposal from
    ``word_prop_rows``, doc proposal from ``doc_prop_rows`` (both with
    ``s`` included, as the tables were built)."""
    n, k = ckt_rows.shape
    alpha = np.broadcast_to(np.asarray(alpha, np.float64), (k,))
    e = np.zeros((n, k))
    e[np.arange(n), s] = 1.0
    pi = ((cdk_rows - e + alpha) * (ckt_rows - e + beta)
          / (np.asarray(ck, np.float64)[None, :] - e + vbeta))
    qw = SCALE * np.asarray(word_prop_rows, np.float64) + grid_prior(beta)
    qd = (SCALE * np.asarray(doc_prop_rows, np.float64)
          + grid_prior(alpha)[None, :])
    qw = qw / qw.sum(axis=1, keepdims=True)
    qd = qd / qd.sum(axis=1, keepdims=True)
    p = e.copy()
    for _ in range(cycles):
        p = mh_step(p, pi, qw)
        p = mh_step(p, pi, qd)
    return p
