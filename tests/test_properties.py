"""Extra property-based tests on system invariants (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.counts import build_counts, check_invariants
from repro.core.engine import ModelParallelLDA
from repro.data.corpus import bigram_corpus, from_documents, from_texts
from repro.data.sharding import shard_documents, worker_shard
from repro.data.synthetic import synthetic_corpus
from repro.models.common import apply_rope


# -- data pipeline -----------------------------------------------------------

@given(st.integers(0, 2**31 - 1), st.integers(1, 200), st.integers(2, 16))
@settings(max_examples=20, deadline=None)
def test_document_sharding_partitions(seed, num_docs, workers):
    assignment = shard_documents(num_docs, workers)
    all_docs = np.concatenate(assignment)
    assert sorted(all_docs.tolist()) == list(range(num_docs))
    sizes = [a.shape[0] for a in assignment]
    assert max(sizes) - min(sizes) <= 1           # balanced


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_worker_shards_cover_corpus(seed):
    corpus, _, _ = synthetic_corpus(20, 50, 4, 15, seed=seed)
    workers = 3
    seen = np.zeros(corpus.num_tokens, int)
    for w in range(workers):
        s = worker_shard(corpus, w, workers)
        seen[s.token_id] += 1
    np.testing.assert_array_equal(seen, 1)        # exactly-once cover


def test_bigram_corpus_matches_paper_construction():
    corpus = from_documents([[0, 1, 2], [1, 2]], vocab_size=3)
    # doc0: (0,1), (1,2); doc1: (1,2) -> 2 unique phrases, 3 occurrences.
    # The paper's Wiki-bigram AUGMENTS the vocabulary (§5): unigrams kept,
    # phrase ids appended above V.
    big = bigram_corpus(corpus)
    assert big.num_tokens == 5 + 3
    assert big.vocab_size == 3 + 2
    # replace=True is the bigram-only escape hatch (the old behaviour)
    rep = bigram_corpus(corpus, replace=True)
    assert rep.num_tokens == 3
    assert rep.vocab_size == 2


def test_from_texts_roundtrip():
    corpus = from_texts(["the cat sat", "the dog sat"])
    assert corpus.vocab_size == 4
    assert corpus.num_tokens == 6
    corpus.validate()


# -- RoPE ----------------------------------------------------------------------

@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_rope_preserves_norm_and_relative_phase(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(1, 4, 2, 16)).astype(np.float32))
    pos = jnp.asarray([[0, 1, 5, 9]], dtype=jnp.int32)
    y = apply_rope(x, pos, 10000.0)
    # rotation: per-token norms preserved
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1),
                               rtol=1e-5)
    # relative property: <q_i, k_j> depends only on i-j
    q = jnp.asarray(rng.normal(size=(1, 1, 1, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 1, 1, 16)).astype(np.float32))
    def dot_at(pi, pj):
        qq = apply_rope(q, jnp.asarray([[pi]], jnp.int32), 10000.0)
        kk = apply_rope(k, jnp.asarray([[pj]], jnp.int32), 10000.0)
        return float(jnp.sum(qq * kk))
    assert abs(dot_at(7, 3) - dot_at(14, 10)) < 1e-3


# -- engine invariants under adversarial corpora -------------------------------

@given(st.integers(0, 2**31 - 1), st.integers(2, 5))
@settings(max_examples=5, deadline=None)
def test_engine_invariants_random_corpus(seed, workers):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(50, 300))
    from repro.data.corpus import Corpus
    corpus = Corpus(rng.integers(0, 12, n).astype(np.int32),
                    rng.integers(0, 31, n).astype(np.int32), 12, 31)
    lda = ModelParallelLDA(corpus, num_topics=5, num_workers=workers,
                           seed=seed)
    lda.run(2)
    check_invariants(lda.gather_counts(), n)


@given(st.integers(0, 2**31 - 1), st.integers(1, 3), st.integers(1, 3),
       st.integers(1, 2))
@settings(max_examples=5, deadline=None)
def test_hybrid_engine_invariants_random_corpus(seed, d, m, s):
    """The 2D grid preserves the count invariants and the rebuild-from-z
    identity on adversarial corpora for any small (D, M, S)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(50, 300))
    from repro.core.counts import build_counts
    from repro.data.corpus import Corpus
    corpus = Corpus(rng.integers(0, 12, n).astype(np.int32),
                    rng.integers(0, 31, n).astype(np.int32), 12, 31)
    lda = ModelParallelLDA(corpus, num_topics=5, num_workers=m, seed=seed,
                           data_parallel=d, blocks_per_worker=s)
    lda.run(2)
    state = lda.gather_counts()
    check_invariants(state, n)
    rebuilt = build_counts(corpus.doc, corpus.word, lda.assignments(),
                           12, 31, 5)
    np.testing.assert_array_equal(np.asarray(rebuilt.ckt),
                                  np.asarray(state.ckt))


def test_single_doc_single_word_degenerate():
    """Degenerate corpora must not break the schedule or the samplers."""
    from repro.data.corpus import Corpus
    corpus = Corpus(np.zeros(10, np.int32), np.zeros(10, np.int32), 1, 1)
    lda = ModelParallelLDA(corpus, num_topics=3, num_workers=2, seed=0)
    lda.run(2)
    state = lda.gather_counts()
    check_invariants(state, 10)
    assert int(np.asarray(state.ckt)[0].sum()) == 10
