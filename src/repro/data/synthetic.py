"""Ground-truth LDA corpus generator.

Samples a corpus from the LDA generative process with known topics so that
tests can check both likelihood ascent and *recovery* of planted
structure (``metrics.topic_recovery_score``).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.data.corpus import Corpus


def synthetic_corpus(num_docs: int, vocab_size: int, num_topics: int,
                     doc_len: int, alpha: float = 0.1, beta: float = 0.01,
                     seed: int = 0, peaked: bool = True
                     ) -> Tuple[Corpus, np.ndarray, np.ndarray]:
    """Returns (corpus, true_phi [K,V], true_theta [D,K]).

    ``peaked=True`` draws topics with near-disjoint support (each topic owns
    a contiguous word band plus Dirichlet noise), making recovery checkable.
    """
    rng = np.random.default_rng(seed)
    if peaked:
        phi = rng.dirichlet([beta] * vocab_size, size=num_topics)
        band = max(vocab_size // num_topics, 1)
        boost = np.zeros((num_topics, vocab_size))
        for k in range(num_topics):
            lo = (k * band) % vocab_size
            boost[k, lo:lo + band] = 1.0
        phi = 0.3 * phi + 0.7 * boost / np.maximum(
            boost.sum(axis=1, keepdims=True), 1)
    else:
        phi = rng.dirichlet([beta * 10] * vocab_size, size=num_topics)
    theta = rng.dirichlet([alpha] * num_topics, size=num_docs)

    lengths = rng.poisson(doc_len, size=num_docs).clip(min=2)
    n = int(lengths.sum())
    doc = np.repeat(np.arange(num_docs, dtype=np.int32), lengths)
    # vectorized ancestral sampling
    zs = np.concatenate([
        rng.choice(num_topics, size=l, p=theta[d])
        for d, l in enumerate(lengths)])
    u = rng.random(n)
    cdf = np.cumsum(phi, axis=1)
    word = np.empty(n, np.int32)
    for k in range(num_topics):
        m = zs == k
        word[m] = np.searchsorted(cdf[k], u[m], side="right").clip(
            max=vocab_size - 1)
    corpus = Corpus(doc, word, num_docs, vocab_size)
    corpus.validate()
    return corpus, phi, theta


def zipf_cdf(vocab_size: int, zipf_a: float = 1.1) -> np.ndarray:
    """CDF of the bounded Zipf word law: ``p(w) ∝ (w + 1)^-a``, ids in
    frequency order."""
    freq = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64) ** zipf_a
    return np.cumsum(freq / freq.sum())


def zipf_corpus(num_docs: int, vocab_size: int, doc_len: int,
                zipf_a: float = 1.1, seed: int = 0) -> Corpus:
    """Long-tail unigram corpus in memory, drawn in bulk: Poisson(doc_len)
    document lengths (at least 2) and Zipf(``zipf_a``) word ids — the law
    of ``stream.write_zipf_stream`` with O(V) generator state, so a
    paper-scale corpus (millions of tokens) takes seconds."""
    rng = np.random.default_rng(seed)
    lengths = np.maximum(rng.poisson(doc_len, num_docs), 2)
    doc = np.repeat(np.arange(num_docs, dtype=np.int32), lengths)
    word = np.searchsorted(zipf_cdf(vocab_size, zipf_a),
                           rng.random(doc.shape[0]), side="right")
    return Corpus(doc, np.minimum(word, vocab_size - 1).astype(np.int32),
                  num_docs, vocab_size)
