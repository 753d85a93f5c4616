"""Data-parallel baseline behaviour + the paper's Fig-2 ordering."""
import numpy as np
import pytest

from repro.core.counts import check_invariants
from repro.core.data_parallel import DataParallelLDA, adlda_engine
from repro.core.engine import ModelParallelLDA


def test_dp_invariants_and_ascent(tiny_corpus):
    corpus, _, _ = tiny_corpus
    dp = DataParallelLDA(corpus, num_topics=8, num_workers=4, seed=1)
    ll0 = dp.log_likelihood()
    hist = dp.run(5)
    assert hist[-1]["log_likelihood"] > ll0
    check_invariants(dp.gather_counts(), corpus.num_tokens)


def test_dp_staleness_error_positive(tiny_corpus):
    """DP samples from stale copies — its reconciliation error is strictly
    positive, while MP's word-topic error is zero by construction."""
    corpus, _, _ = tiny_corpus
    dp = DataParallelLDA(corpus, num_topics=8, num_workers=4, seed=1)
    dp.step()
    assert dp.model_error() > 0


def test_more_syncs_reduce_staleness(small_corpus):
    corpus, _, _ = small_corpus
    errs = []
    for s in (1, 4):
        dp = DataParallelLDA(corpus, num_topics=10, num_workers=4, seed=3,
                             syncs_per_iter=s)
        dp.step()
        errs.append(dp.model_error())
    assert errs[1] < errs[0]


def test_mp_converges_at_least_as_fast_per_iteration(small_corpus):
    """Fig 2a: per-iteration likelihood of MP dominates DP early on."""
    corpus, _, _ = small_corpus
    mp = ModelParallelLDA(corpus, num_topics=10, num_workers=8, seed=5)
    dp = DataParallelLDA(corpus, num_topics=10, num_workers=8, seed=5)
    h_mp = mp.run(6)
    h_dp = dp.run(6)
    mp_ll = [h["log_likelihood"] for h in h_mp]
    dp_ll = [h["log_likelihood"] for h in h_dp]
    # compare the early trajectory where staleness hurts most
    wins = sum(a >= b for a, b in zip(mp_ll[:4], dp_ll[:4]))
    assert wins >= 3, (mp_ll, dp_ll)


def test_dp_memory_is_flat_mp_shrinks(small_corpus):
    """Fig 4a: per-worker model bytes — DP O(VK) flat, MP O(VK/M)."""
    corpus, _, _ = small_corpus
    for m in (2, 4):
        mp = ModelParallelLDA(corpus, num_topics=10, num_workers=m)
        dp = DataParallelLDA(corpus, num_topics=10, num_workers=m)
        mp_bytes = np.asarray(mp.state.ckt)[0].nbytes
        dp_bytes = np.asarray(dp.ckt_local)[0].nbytes
        assert dp_bytes == corpus.vocab_size * 10 * 4
        assert mp_bytes == mp.partition.block_size * 10 * 4
        assert mp_bytes <= dp_bytes // m + 10 * 4 * mp.partition.block_size // 100 + 40


def test_hybrid_round_sync_staleness_below_adlda_baseline(small_corpus):
    """Fig 2/3 ordering, pinned in CI: the per-round-synced hybrid engine
    reconciles S·M times per iteration and confines parallelization error
    to {C_k} within a round, so its normalized staleness must stay at or
    below the AD-LDA baseline's (one reconciliation per iteration) for the
    same total worker count."""
    corpus, _, _ = small_corpus
    dp = DataParallelLDA(corpus, num_topics=10, num_workers=4, seed=3,
                         syncs_per_iter=1)
    hybrid = ModelParallelLDA(corpus, num_topics=10, num_workers=2,
                              data_parallel=2, seed=3)
    for _ in range(2):
        dp.step()
        hybrid.step()
    assert hybrid.delta_error() <= dp.model_error(), (
        hybrid.delta_error(), dp.model_error())


def test_adlda_engine_is_degenerate_hybrid(small_corpus):
    """The engine-built AD-LDA (M=1) exposes the same staleness model as
    the standalone baseline: positive pre-sync error at one sync per
    iteration, shrinking as blocks_per_worker adds sync points (the
    syncs_per_iter analogue)."""
    corpus, _, _ = small_corpus
    errs = []
    for s in (1, 4):
        eng = adlda_engine(corpus, num_topics=10, num_replicas=4, seed=3,
                           blocks_per_worker=s)
        eng.step()
        errs.append(eng.delta_error())
    assert errs[0] > 0
    assert errs[1] < errs[0]
