"""Fold-in / serving subsystem (DESIGN.md §11).

Layers, mirroring how the trainer is validated:

* **structural** — the batched device fold-in equals the serial host
  oracle (`kvstore.fold_in_oracle`) draw-for-draw, for snapshots taken
  from engines trained at several (D, M, S) geometries; the MH pair
  (`mh`, `mh_pallas`) draws bit-identically; padding (the serving
  bucket mechanism) provably never perturbs real queries.
* **statistical** — held-out doc-completion perplexity decreases over
  training iterations on the planted-topics corpus, and a zero-count
  snapshot scores exactly the uninformative ceiling V.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine.api import ModelParallelLDA
from repro.core.infer import (FoldInResult, ModelSnapshot,
                              _fold_in_mh_sweeps, _fold_in_scan_sweeps,
                              _fold_in_sparse_sweeps, fold_in,
                              init_query_cdk, load_snapshot, pack_queries,
                              theta_from_cdk)
from repro.core.kvstore import fold_in_oracle
from repro.core.likelihood import doc_completion_perplexity
from repro.data.corpus import split_corpus
from repro.serve.topic_infer import TopicInferenceServer, bucket_size

K = 8


def _train_snapshot(corpus, d=1, s=1, iters=2, seed=0):
    lda = ModelParallelLDA(corpus, K, num_workers=2, seed=seed,
                           blocks_per_worker=s, data_parallel=d)
    lda.run(iters)
    return lda, lda.snapshot()


def _query_arrays(vocab, q=4, t=18, sweeps=3, seed=1):
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, t + 1, size=q)
    docs = [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]
    word, mask = pack_queries(docs, t_pad=t)
    z0 = rng.integers(0, K, size=word.shape).astype(np.int32)
    u = rng.random((sweeps, *word.shape), np.float32)
    return docs, word, mask, z0, u


@pytest.fixture(scope="module")
def snap(tiny_corpus):
    corpus, _, _ = tiny_corpus
    return _train_snapshot(corpus)[1]


# ---------------------------------------------------------------------------
# Snapshot export
# ---------------------------------------------------------------------------

def test_snapshot_consistency(tiny_corpus):
    corpus, _, _ = tiny_corpus
    lda, snap = _train_snapshot(corpus)
    state = lda.gather_counts()
    np.testing.assert_array_equal(snap.ckt, np.asarray(state.ckt))
    np.testing.assert_array_equal(snap.ck, snap.ckt.sum(axis=0))
    assert snap.vocab_size == corpus.vocab_size
    assert snap.num_topics == K
    assert snap.ck.sum() == corpus.num_tokens
    # φ̂ᵀ columns are normalized over the vocabulary
    np.testing.assert_allclose(snap.word_term().sum(axis=0),
                               np.ones(K), rtol=1e-5)


def test_snapshot_save_load_rebuilds_tables_bitwise(tmp_path, snap):
    """Persistence drops the tables; the bit-deterministic builder must
    reproduce them exactly on load (why the npz stays counts-only)."""
    path = str(tmp_path / "snap")
    snap.save(path)
    out = load_snapshot(path + ".npz")
    np.testing.assert_array_equal(out.ckt, snap.ckt)
    np.testing.assert_array_equal(out.ck, snap.ck)
    np.testing.assert_array_equal(out.alpha, snap.alpha)
    assert out.beta == snap.beta
    np.testing.assert_array_equal(out.ensure_tables(),
                                  snap.ensure_tables())


# ---------------------------------------------------------------------------
# Engine == host oracle, draw for draw
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,s", [(1, 1), (1, 2), (2, 1), (2, 2)])
@pytest.mark.parametrize("sampler", ["scan", "mh"])
def test_fold_in_matches_host_oracle(tiny_corpus, d, s, sampler):
    """Batched device fold-in == serial host replay, bitwise, against
    snapshots exported from engines trained across the (D, M, S) grid —
    the serving-side version of the trainer's oracle anchor."""
    corpus, _, _ = tiny_corpus
    _, snap_g = _train_snapshot(corpus, d=d, s=s)
    _, word, mask, z0, u = _query_arrays(corpus.vocab_size)
    res = fold_in(snap_g, word, mask, sampler=sampler, z0=z0, u=u)
    cdk_o, z_o = fold_in_oracle(snap_g, word, mask, z0, u, sampler=sampler)
    np.testing.assert_array_equal(res.z, z_o)
    np.testing.assert_array_equal(res.cdk, cdk_o)


def test_fold_in_mh_pallas_bitwise(snap, tiny_corpus):
    """The MH pair draws identically at serve time, as in training."""
    corpus, _, _ = tiny_corpus
    _, word, mask, z0, u = _query_arrays(corpus.vocab_size)
    a = fold_in(snap, word, mask, sampler="mh", z0=z0, u=u)
    b = fold_in(snap, word, mask, sampler="mh_pallas", z0=z0, u=u)
    np.testing.assert_array_equal(a.z, b.z)
    np.testing.assert_array_equal(a.cdk, b.cdk)


@pytest.mark.parametrize("sampler", ["scan", "mh"])
def test_fold_in_padding_invariance(snap, tiny_corpus, sampler):
    """Growing the bucket (extra masked rows/columns filled with garbage)
    must not change any real query's draws — the property that makes the
    serving buckets a pure latency knob."""
    corpus, _, _ = tiny_corpus
    _, word, mask, z0, u = _query_arrays(corpus.vocab_size, q=3, t=12)
    base = fold_in(snap, word, mask, sampler=sampler, z0=z0, u=u)

    rng = np.random.default_rng(99)
    q, t = word.shape
    q2, t2 = q + 3, t + 9
    word2 = rng.integers(0, corpus.vocab_size, (q2, t2)).astype(np.int32)
    z02 = rng.integers(0, K, (q2, t2)).astype(np.int32)
    u2 = rng.random((u.shape[0], q2, t2), np.float32)
    mask2 = np.zeros((q2, t2), bool)
    word2[:q, :t] = word
    z02[:q, :t] = z0
    u2[:, :q, :t] = u
    mask2[:q, :t] = mask
    grown = fold_in(snap, word2, mask2, sampler=sampler, z0=z02, u=u2)
    np.testing.assert_array_equal(grown.z[:q, :t], base.z)
    np.testing.assert_array_equal(grown.cdk[:q], base.cdk)


def _host_operand_fold_in(snap, sampler, word, mask, z0, u):
    """Fold-in with every operand converted from the host on the call:
    the sweeps fed the numpy views of a snapshot that holds no device
    copy."""
    k = snap.num_topics
    cdk0 = init_query_cdk(z0, mask, k)
    if sampler == "scan":
        ops = (cdk0, snap.word_term(), word, z0, mask, u, snap.alpha)
        cdk, z = _fold_in_scan_sweeps(*map(jnp.asarray, ops))
    elif sampler == "sparse":
        ops = (cdk0, snap.word_term(), *snap.sparse_state(), word, z0, mask,
               u)
        cdk, z = _fold_in_sparse_sweeps(*map(jnp.asarray, ops),
                                        dcap=min(k, word.shape[1]))
    else:
        ops = (cdk0, snap.ckt, snap.ck, snap.ensure_tables(), word, z0,
               mask, u, snap.alpha, np.float32(snap.beta),
               np.float32(snap.vbeta))
        cdk, z = _fold_in_mh_sweeps(*map(jnp.asarray, ops),
                                    sampler_mode=sampler)
    cdk = np.asarray(cdk)
    return cdk, np.asarray(z), theta_from_cdk(cdk, snap.alpha)


@pytest.mark.parametrize("sampler", ["scan", "sparse", "mh"])
def test_resident_snapshot_folds_in_bitwise(snap, tiny_corpus, sampler):
    """The device-resident snapshot gives the host-operand path's
    ``cdk``, ``z`` and ``θ`` bit for bit: the device copy holds the host
    views' bytes, and MH tables built on the device equal the host's."""
    corpus, _, _ = tiny_corpus
    _, word, mask, z0, u = _query_arrays(corpus.vocab_size)

    def copy():
        return ModelSnapshot.from_counts(snap.ckt, snap.ck, snap.alpha,
                                         snap.beta)

    on_host = copy()
    want = _host_operand_fold_in(on_host, sampler, word, mask, z0, u)
    assert on_host.placements == 0
    resident = copy()
    TopicInferenceServer(resident, sampler=sampler)       # places it
    fold_in(resident, word, mask, sampler=sampler, z0=z0, u=u)
    got = fold_in(resident, word, mask, sampler=sampler, z0=z0, u=u)
    assert resident.placements == 1
    assert got.h2d_bytes == sum(
        x.nbytes for x in (init_query_cdk(z0, mask, K), word, z0, mask, u))
    for g, w in zip((got.cdk, got.z, got.theta), want):
        np.testing.assert_array_equal(g, w)


def test_fold_in_validation(snap):
    word = np.zeros((2, 4), np.int32)
    mask = np.ones((2, 4), bool)
    with pytest.raises(ValueError, match="sampler"):
        fold_in(snap, word, mask, sampler="batched")
    with pytest.raises(ValueError, match="shape"):
        fold_in(snap, word, np.ones((2, 5), bool))


def test_fold_in_result_shapes_and_theta(snap, tiny_corpus):
    corpus, _, _ = tiny_corpus
    docs, word, mask, z0, u = _query_arrays(corpus.vocab_size)
    res = fold_in(snap, word, mask, sampler="mh", z0=z0, u=u)
    assert isinstance(res, FoldInResult)
    assert res.cdk.shape == (word.shape[0], K)
    assert res.z.shape == word.shape
    # per-doc token conservation: cdk row sums == real token counts
    np.testing.assert_array_equal(res.cdk.sum(axis=1), mask.sum(axis=1))
    np.testing.assert_allclose(res.theta.sum(axis=1), 1.0, rtol=1e-12)
    assert (res.theta > 0).all()
    # helpers agree with the result
    np.testing.assert_array_equal(
        init_query_cdk(res.z, mask, K).sum(axis=1), mask.sum(axis=1))
    np.testing.assert_allclose(res.theta,
                               theta_from_cdk(res.cdk, snap.alpha))


# ---------------------------------------------------------------------------
# Perplexity estimator
# ---------------------------------------------------------------------------

def test_uniform_snapshot_perplexity_is_vocab_size():
    """Zero counts -> every word scores exactly 1/V -> perplexity == V,
    the uninformative ceiling (closed-form check of the estimator)."""
    v = 120
    snap0 = ModelSnapshot.from_counts(np.zeros((v, K), np.int32),
                                      alpha=0.1, beta=0.01)
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, v, size=12) for _ in range(5)]
    out = doc_completion_perplexity(snap0, docs, num_sweeps=2)
    np.testing.assert_allclose(out["perplexity"], v, rtol=1e-5)
    assert out["tokens_scored"] == 5 * 6


def test_holdout_perplexity_decreases_with_training(small_corpus):
    """Statistical sanity: on the planted-topics corpus, doc-completion
    perplexity of held-out docs falls as the model trains — the
    convergence signal training log-likelihood cannot provide."""
    corpus, _, _ = small_corpus
    train, held = split_corpus(corpus, 20)
    docs = held.doc_words()
    lda = ModelParallelLDA(train, 10, num_workers=2, seed=0,
                           sampler_mode="batched")
    lda.step()
    early = doc_completion_perplexity(lda.snapshot(), docs,
                                      num_sweeps=5, seed=3)
    lda.run(11)
    late = doc_completion_perplexity(lda.snapshot(), docs,
                                     num_sweeps=5, seed=3)
    assert np.isfinite(early["perplexity"])
    assert late["perplexity"] < 0.95 * early["perplexity"], \
        (early["perplexity"], late["perplexity"])
    assert late["perplexity"] < train.vocab_size   # beats the ceiling


def test_perplexity_requires_scorable_tokens(snap):
    with pytest.raises(ValueError, match="score"):
        doc_completion_perplexity(snap, [np.zeros(0, np.int32)])


# ---------------------------------------------------------------------------
# Serving facade
# ---------------------------------------------------------------------------

def test_bucket_size():
    assert [bucket_size(n, 8) for n in (1, 8, 9, 16, 33)] == \
        [8, 8, 16, 16, 64]
    assert bucket_size(3) == 4


def test_server_buckets_batches_and_serves(snap, tiny_corpus):
    corpus, _, _ = tiny_corpus
    rng = np.random.default_rng(5)
    server = TopicInferenceServer(snap, sampler="mh", num_sweeps=3, seed=0)
    docs = [rng.integers(0, corpus.vocab_size, size=n) for n in (5, 9, 17)]
    assert server.bucket_shape(docs) == (4, 32)
    theta = server.infer(docs)
    assert theta.shape == (3, K)
    np.testing.assert_allclose(theta.sum(axis=1), 1.0, rtol=1e-12)
    # a second batch landing in the same bucket reuses the compiled shape
    more = [rng.integers(0, corpus.vocab_size, size=n) for n in (20, 30)]
    server.infer(more)
    assert server.bucket_calls[(4, 32)] == 1
    assert server.bucket_calls[(2, 32)] == 1
    server.infer(docs)
    assert server.bucket_calls[(4, 32)] == 2
    assert server.docs_served == 8
    one = server.infer_one(docs[0])
    assert one.shape == (K,)
    ppl = server.perplexity(docs)
    assert np.isfinite(ppl["perplexity"])


def test_server_empty_batch(snap):
    server = TopicInferenceServer(snap, sampler="scan")
    assert server.infer([]).shape == (0, K)


def test_server_scan_matches_direct_fold_in(snap, tiny_corpus):
    """The server is pure orchestration: same snapshot, same rng stream,
    same bucket -> identical mixtures to calling fold_in directly."""
    corpus, _, _ = tiny_corpus
    rng = np.random.default_rng(7)
    docs = [rng.integers(0, corpus.vocab_size, size=n) for n in (6, 11)]
    server = TopicInferenceServer(snap, sampler="scan", num_sweeps=4,
                                  seed=42)
    theta = server.infer(docs)
    word, mask = pack_queries(docs, t_pad=16, q_pad=2)
    res = fold_in(snap, word, mask, num_sweeps=4, sampler="scan",
                  rng=np.random.default_rng(42))
    np.testing.assert_allclose(theta, res.theta[:2])

# ---------------------------------------------------------------------------
# Server edge cases (PR 8 backfill) + the scheduler's draw-injection API
# ---------------------------------------------------------------------------

def test_server_empty_doc_in_batch_gets_prior_mixture(snap, tiny_corpus):
    """A zero-length doc is all padding: its mixture is the normalized
    prior (no evidence), and it must not perturb its batchmates."""
    corpus, _, _ = tiny_corpus
    rng = np.random.default_rng(9)
    doc = rng.integers(0, corpus.vocab_size, size=7)
    server = TopicInferenceServer(snap, sampler="scan", num_sweeps=3,
                                  seed=0)
    theta = server.infer([doc, np.zeros(0, np.int32)])
    assert theta.shape == (2, K)
    np.testing.assert_allclose(theta[1], snap.alpha / snap.alpha.sum(),
                               rtol=1e-12)
    np.testing.assert_allclose(theta.sum(axis=1), 1.0, rtol=1e-12)


def test_server_batch_of_one(snap, tiny_corpus):
    corpus, _, _ = tiny_corpus
    rng = np.random.default_rng(10)
    doc = rng.integers(0, corpus.vocab_size, size=5)
    server = TopicInferenceServer(snap, sampler="scan", seed=3)
    theta = server.infer([doc])
    assert theta.shape == (1, K)
    assert server.bucket_calls == {(1, 8): 1}


def test_server_doc_longer_than_min_bucket(snap, tiny_corpus):
    """A doc past every warmed bucket pads into the next power of two —
    a fresh compile, never an error or a truncation."""
    corpus, _, _ = tiny_corpus
    rng = np.random.default_rng(11)
    doc = rng.integers(0, corpus.vocab_size, size=100)
    server = TopicInferenceServer(snap, sampler="scan", seed=4)
    assert server.bucket_shape([doc]) == (1, 128)
    theta = server.infer([doc])
    assert theta.shape == (1, K)
    assert np.isfinite(theta).all()
    assert server.bucket_calls == {(1, 128): 1}


@pytest.mark.parametrize("sampler", ["scan", "mh", "sparse"])
def test_infer_with_draws_bucket_invariance(snap, tiny_corpus, sampler):
    """The scheduler's foundation: with per-doc draws supplied, a doc's
    mixture is bitwise the same served alone in a (1, 8) bucket or
    packed with strangers into a (4, 32) bucket — for every sampler
    family the scheduler can bind."""
    corpus, _, _ = tiny_corpus
    rng = np.random.default_rng(12)
    sweeps = 3
    docs = [rng.integers(0, corpus.vocab_size, size=n).astype(np.int32)
            for n in (6, 8, 17)]
    z0s = [rng.integers(0, K, size=len(d)).astype(np.int32) for d in docs]
    us = [rng.random((sweeps, len(d)), dtype=np.float32) for d in docs]
    server = TopicInferenceServer(snap, sampler=sampler, num_sweeps=sweeps,
                                  seed=0)
    batched = server.infer_with_draws(docs, z0s, us)
    for i, d in enumerate(docs):
        alone = server.infer_with_draws([d], [z0s[i]], [us[i]])
        np.testing.assert_array_equal(alone[0], batched[i])


def test_infer_with_draws_validation(snap, tiny_corpus):
    corpus, _, _ = tiny_corpus
    rng = np.random.default_rng(13)
    doc = rng.integers(0, corpus.vocab_size, size=5).astype(np.int32)
    server = TopicInferenceServer(snap, sampler="scan", num_sweeps=2)
    assert server.infer_with_draws([], [], []).shape == (0, K)
    z0 = rng.integers(0, K, size=5).astype(np.int32)
    u = rng.random((2, 5), dtype=np.float32)
    with pytest.raises(ValueError, match="one z0/u row per doc"):
        server.infer_with_draws([doc], [z0, z0], [u, u])
    with pytest.raises(ValueError, match="draws must be"):
        server.infer_with_draws([doc], [z0[:3]], [u])
    with pytest.raises(ValueError, match="draws must be"):
        server.infer_with_draws([doc], [z0], [u[:1]])
