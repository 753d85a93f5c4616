"""Model-parallel engine semantics — the paper's central claims as tests.

The key test replays the MP schedule *serially* with the exact same
per-(round, worker) uniforms and frozen-``C_k``-per-round semantics, and
asserts bit-identical results: "parallelizing over the disjoint blocks
produces exactly the same result as the serial execution" (paper §1).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.counts import build_counts, check_invariants
from repro.core.invindex import scatter_assignments
from repro.core.metrics import topic_recovery_score
from repro.core.engine import ModelParallelLDA
from repro.core.sampler import gibbs_sweep_np, sweep_block_scan
from repro.core import schedule as sched


def _serial_replay(lda: ModelParallelLDA, u: np.ndarray):
    """Execute one MP iteration serially, worker-by-worker per round,
    using the same jitted block sampler and the same uniforms, with the
    engine's frozen-``C_k``-within-round semantics.  Follows the
    ``S·M``-round pipeline schedule, so it is the oracle for any
    ``blocks_per_worker``."""
    m, s_ = lda.num_workers, lda.blocks_per_worker
    cdk = np.array(lda.state.cdk)
    ckt = np.array(lda.state.ckt)             # [M, S, Vb, K] slot queues
    bid = np.array(lda.state.block_id)        # [M, S]
    blocks = {int(bid[w, s]): ckt[w, s].copy()
              for w in range(m) for s in range(s_)}
    ck_synced = np.array(lda.state.ck_synced)
    z = np.array(lda.state.z)
    doc, woff, mask = (np.array(lda.doc), np.array(lda.woff),
                       np.array(lda.mask))
    for r in range(lda.num_rounds):
        deltas = np.zeros_like(ck_synced)
        for w in range(m):
            b = sched.block_for(w, r, m, s_)
            ck_local = ck_synced.copy()
            out = sweep_block_scan(
                jnp.asarray(cdk[w]), jnp.asarray(blocks[b]),
                jnp.asarray(ck_local),
                jnp.asarray(doc[w, b]), jnp.asarray(woff[w, b]),
                jnp.asarray(z[w, b]), jnp.asarray(mask[w, b]),
                jnp.asarray(u[r, w]), lda.alpha,
                jnp.float32(lda.beta), jnp.float32(lda.vbeta))
            cdk[w] = np.asarray(out[0])
            blocks[b] = np.asarray(out[1])
            deltas += np.asarray(out[2]) - ck_local
            z[w, b] = np.asarray(out[3])
        ck_synced = ck_synced + deltas
    # after S·M rounds every block is back at its home slot (s·M + w)
    ckt_out = np.stack([np.stack([blocks[s * m + w] for s in range(s_)])
                        for w in range(m)])
    return cdk, ckt_out, ck_synced, z


def test_parallel_equals_serial_bitexact(tiny_corpus):
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=4, seed=11)
    rng_state = lda._rng.bit_generator.state
    u = np.asarray(lda._uniforms())          # consumes the rng
    lda._rng.bit_generator.state = rng_state  # rewind so step() reuses it
    ref_cdk, ref_ckt, ref_ck, ref_z = _serial_replay(lda, u)
    lda.step()
    # blocks rotated home after S·M rounds: slot (w, s) == block s·M + w
    np.testing.assert_array_equal(np.array(lda.state.cdk), ref_cdk)
    np.testing.assert_array_equal(np.array(lda.state.ckt), ref_ckt)
    np.testing.assert_array_equal(np.array(lda.state.ck_synced), ref_ck)
    np.testing.assert_array_equal(np.array(lda.state.z), ref_z)


def test_single_worker_equals_plain_serial_cgs(tiny_corpus):
    """M=1: no partitioning, no drift — engine must equal textbook CGS."""
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=1, seed=3)
    rng_state = lda._rng.bit_generator.state
    u = np.asarray(lda._uniforms())[0, 0]
    lda._rng.bit_generator.state = rng_state
    idx = lda.indexes[0]
    n = int(idx.mask.sum())
    st0 = lda.gather_counts()
    cdk, ckt, ck = (np.array(st0.cdk), np.array(st0.ckt), np.array(st0.ck))
    vpad = lda.partition.padded_vocab
    ckt_pad = np.zeros((vpad, 8), np.int32)
    ckt_pad[:ckt.shape[0]] = ckt
    z0 = np.array(lda.state.z)[0, 0]
    z_ref = gibbs_sweep_np(cdk, ckt_pad, ck,
                           idx.doc[0, :n], idx.word_off[0, :n], z0[:n],
                           u[:n], np.asarray(lda.alpha), lda.beta,
                           use_eq3=True)
    lda.step()
    z_eng = np.array(lda.state.z)[0, 0, :n]
    assert (z_eng == z_ref).mean() > 0.995   # float-order tolerance only


def test_invariants_after_many_iterations(tiny_corpus):
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=4, seed=2)
    lda.run(4)
    state = lda.gather_counts()
    check_invariants(state, corpus.num_tokens)
    # z-consistency: counts rebuilt from assignments match engine counts
    z = lda.assignments()
    rebuilt = build_counts(corpus.doc, corpus.word, z, corpus.num_docs,
                           corpus.vocab_size, 8)
    np.testing.assert_array_equal(np.asarray(rebuilt.ckt),
                                  np.asarray(state.ckt))
    np.testing.assert_array_equal(np.asarray(rebuilt.cdk),
                                  np.asarray(state.cdk))


@pytest.mark.parametrize("mode", ["scan", "scan_eq1", "batched", "pallas"])
def test_likelihood_ascends_all_sampler_modes(tiny_corpus, mode):
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=4, seed=5,
                           sampler_mode=mode)
    ll0 = lda.log_likelihood()
    hist = lda.run(6)
    assert hist[-1]["log_likelihood"] > ll0 + 1000
    check_invariants(lda.gather_counts(), corpus.num_tokens)


def test_pallas_mode_matches_batched_mode(tiny_corpus):
    corpus, _, _ = tiny_corpus
    a = ModelParallelLDA(corpus, 8, 4, seed=1, sampler_mode="batched")
    b = ModelParallelLDA(corpus, 8, 4, seed=1, sampler_mode="pallas")
    for _ in range(2):
        a.step(); b.step()
    np.testing.assert_array_equal(np.asarray(a.gather_counts().ckt),
                                  np.asarray(b.gather_counts().ckt))


def test_delta_error_small_and_shrinking(small_corpus):
    """Fig 3: Δ_{r,i} is tiny (≪ the [0,2] range) and does not grow."""
    corpus, _, _ = small_corpus
    lda = ModelParallelLDA(corpus, num_topics=10, num_workers=4, seed=9)
    lda.step()
    first = lda.delta_error()
    for _ in range(4):
        lda.step()
    last = lda.delta_error()
    assert first < 0.1
    assert last <= first * 1.5
    assert last < 0.05


def test_worker_count_does_not_change_distribution(small_corpus):
    """Likelihood after T iterations is statistically the same for any M —
    model-parallelism changes the schedule, not the inference."""
    corpus, _, _ = small_corpus
    lls = []
    for m in (1, 2, 4):
        lda = ModelParallelLDA(corpus, num_topics=10, num_workers=m, seed=13)
        lda.run(12)
        lls.append(lda.log_likelihood())
    spread = max(lls) - min(lls)
    assert spread < 0.03 * abs(np.mean(lls)), (lls, spread)


def test_topic_recovery_on_planted_corpus(small_corpus):
    corpus, phi, _ = small_corpus
    lda = ModelParallelLDA(corpus, num_topics=10, num_workers=4, seed=17)
    lda.run(15)
    score = topic_recovery_score(np.asarray(lda.gather_counts().ckt), phi)
    assert score > 0.5, score


def test_assignments_roundtrip(tiny_corpus):
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=3, seed=21)
    lda.step()
    z = lda.assignments()
    assert z.shape == (corpus.num_tokens,)
    assert (z >= 0).all() and (z < 8).all()
