"""The one general generator of inputs: corpora, frozen-model counts and
request traces, each a pure function of the seed and a traffic file.
A request trace is drawn by the arrival law that the mix names.

Copied (not imported) from the program so that program changes cannot
move the yardstick: the corpus law from ``repro.data.synthetic.zipf_corpus``;
the open-loop Poisson law (``bench/traffic/open_loop_poisson.py``) from
``repro.serve.traffic.poisson_trace``."""
from __future__ import annotations

import numpy as np

from harness import common


def zipf_cdf(n: int, a: float) -> np.ndarray:
    """CDF of the bounded Zipf law ``p(i) ∝ (i + 1)^-a`` on ``0..n-1``."""
    freq = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** a
    return np.cumsum(freq / freq.sum())


def zipf_ids(rng: np.random.Generator, cdf: np.ndarray, size) -> np.ndarray:
    ids = np.searchsorted(cdf, rng.random(size), side="right")
    return np.minimum(ids, cdf.shape[0] - 1).astype(np.int32)


def corpus(seed: int, num_docs: int, vocab_size: int, mix: dict):
    """``(doc, word)`` token arrays: Poisson(``doc_len_mean``) lengths, at
    least ``doc_len_min``, Zipf(``word_zipf_a``) ids in frequency order."""
    rng = np.random.default_rng(seed)
    lengths = np.maximum(rng.poisson(mix["doc_len_mean"], num_docs),
                         mix["doc_len_min"])
    doc = np.repeat(np.arange(num_docs, dtype=np.int32), lengths)
    word = zipf_ids(rng, zipf_cdf(vocab_size, mix["word_zipf_a"]),
                    doc.shape[0])
    return doc, word


def jax_key(jax, seed: int, stream: int):
    """A JAX key for one named stream of a run's seed (any size of seed)."""
    s = np.random.SeedSequence([int(seed), stream]).generate_state(1)[0]
    return jax.random.key(int(s) & 0x7FFFFFFF)


def model_counts(jax, seed: int, vocab_size: int, num_topics: int,
                 law: dict) -> np.ndarray:
    """Frozen word-topic counts ``[V, K]`` int32, made on the device in one
    jitted call: word ``w`` gets its Zipf(``word_zipf_a``) share of
    ``corpus_tokens`` tokens, spread over topics by a
    Dirichlet(``topic_concentration``) draw and floored."""
    import jax.numpy as jnp
    v, k = vocab_size, num_topics

    @jax.jit
    def make(key):
        r = jnp.arange(1, v + 1, dtype=jnp.float32)
        p = r ** -law["word_zipf_a"]
        n_w = jnp.round(p / p.sum() * law["corpus_tokens"])
        g = jax.random.loggamma(key, law["topic_concentration"], (v, k))
        share = jax.nn.softmax(g, axis=1)
        return jnp.floor(n_w[:, None] * share).astype(jnp.int32)

    return np.asarray(make(jax_key(jax, seed, 1)))


def requests(seed: int, seconds: float, vocab_size: int, mix: dict):
    """The mix's open-loop trace, ``[(t_arrival, tokens), ...]``, drawn by
    its arrival law ``bench/traffic/<law>.py`` (``mix["law"]``): a new
    law is a new file there, and a new mix under a law is data alone."""
    return common.load_module("traffic", mix["law"]).requests(
        seed, seconds, vocab_size, mix)
