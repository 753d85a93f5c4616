"""The ring cell's reference and check (``reference/ring.py``,
``checks/ring_transition.py``): the schedule and the document rule equal
the program's, the transition is ``mh_transition`` at one block, the
round-start counts are recounts, and the check passes a sound ring run
on the CPU and fails it when the ring's rounds are broken."""
import types

import numpy as np
import pytest

from reference import lda as ref
from reference import ring

GRIDS = [(1, 1), (2, 1), (4, 2), (3, 3)]


@pytest.mark.parametrize("m,s", GRIDS)
def test_schedule_equals_the_programs(jax, m, s):
    from repro.core import schedule
    b = m * s
    for r in range(b):
        for w in range(m):
            blk = ring.block_for(w, r, m, s)
            assert blk == schedule.block_for(w, r, m, s)
            assert ring.round_of(w, blk, m, s) == r
    for blk in range(b):
        first = min(r for r in range(b) for w in range(m)
                    if ring.block_for(w, r, m, s) == blk)
        assert ring.first_residency(blk, m) == first == \
            schedule.home_slot(blk, m)


@pytest.mark.parametrize("m,s", GRIDS)
def test_tokens_and_documents_go_where_the_engine_puts_them(jax, m, s):
    from repro.core.engine import state as engine_state
    from repro.data.synthetic import synthetic_corpus
    corpus, _, _ = synthetic_corpus(num_docs=37, vocab_size=90,
                                    num_topics=4, doc_len=12, seed=2)
    layout = engine_state.build_layout(corpus, m, s)
    worker = ring.worker_of_doc(corpus.doc, m)
    rounds = ring.token_rounds(corpus.word, corpus.doc, corpus.vocab_size,
                               m, s)
    for w, (shard, idx) in enumerate(zip(layout.shards, layout.indexes)):
        docs = shard.doc_global[shard.doc_global >= 0]
        np.testing.assert_array_equal(
            docs, np.flatnonzero(ring.worker_of_doc(
                np.arange(corpus.num_docs), m) == w))
        np.testing.assert_array_equal(np.flatnonzero(worker == w),
                                      np.sort(shard.token_id))
        for blk in range(m * s):
            tok = shard.token_id[idx.token_id[blk][idx.mask[blk]]]
            assert (rounds[tok] == ring.round_of(w, blk, m, s)).all()


def _rows(rng, n, k, hi):
    return rng.integers(0, hi, size=(n, k)).astype(np.float64)


def test_ring_transition_is_mh_transition_at_one_block():
    rng = np.random.default_rng(4)
    n, k = 64, 16
    w, d = _rows(rng, n, k, 40), _rows(rng, n, k, 9)
    s = rng.integers(0, k, n)
    w[np.arange(n), s] += 1
    d[np.arange(n), s] += 1
    ck = w.sum(axis=0) + 500
    args = (0.1, 0.01, 0.01 * 300, 2)
    np.testing.assert_array_equal(
        ring.ring_transition(s, w, d, ck, w, d, *args),
        ref.mh_transition(s, w, d, ck, *args))
    stale = ring.ring_transition(s, w, d, ck, _rows(rng, n, k, 40), d, *args)
    np.testing.assert_allclose(stale.sum(axis=1), 1.0)
    assert not np.allclose(stale, ref.mh_transition(s, w, d, ck, *args))


def test_round_start_counts_are_recounts():
    rng = np.random.default_rng(5)
    n, v, dd, k, rounds_n = 3000, 50, 40, 7, 6
    word = rng.integers(0, v, n)
    doc = rng.integers(0, dd, n)
    zb, za = rng.integers(0, k, n), rng.integers(0, k, n)
    rounds = rng.integers(0, rounds_n, n)
    want_w, want_d = word[::97], doc[::89]
    got = list(ring.round_start_counts(word, doc, zb, za, rounds, want_w,
                                       want_d, k, rounds_n))
    assert len(got) == rounds_n
    for r, (ckt, cdk, ck) in enumerate(got):
        z = np.where(rounds < r, za, zb)
        full_w, full_d, full_k = ref.recount(word, doc, z, v, dd, k)
        np.testing.assert_array_equal(ckt, full_w[want_w])
        np.testing.assert_array_equal(cdk, full_d[want_d])
        np.testing.assert_array_equal(ck, full_k)


# ---------------------------------------------------------------------------
# The checks on runs at the rehearsal sizes (four faked devices)
# ---------------------------------------------------------------------------

def _rehearse(jax, workload):
    import importlib

    import run as bench_run
    _, cell, cfg, mix = bench_run.load_cell(workload)
    cfg, mix = bench_run.rehearsal(cfg, mix)
    runner = importlib.import_module(f"harness.{cfg['kind']}")
    return runner.run(jax, cfg, mix, 11, 2.0, cell["chips"])


@pytest.fixture(scope="module")
def ring_run(jax):
    return _rehearse(jax, "ring-pubmed-k1000-4chip")


def test_ring_check_is_mh_transition_at_one_block(jax):
    """On the one-chip cell's run, read as a ring of one worker holding
    one block, the ring's check gives ``mh_transition``'s scores."""
    from checks import mh_transition, ring_transition
    o = _rehearse(jax, "train-pubmed-k1000").check_input
    one = types.SimpleNamespace(**vars(o), num_workers=1,
                                blocks_per_worker=1,
                                doc_to_worker="round_robin")
    got, want = ring_transition.check(one), mh_transition.check(o)
    assert [n for n, *_ in got] == [n for n, *_ in want]
    for (_, val, limit), (_, val_1, limit_1) in zip(got, want):
        assert limit == limit_1
        assert val == pytest.approx(val_1, rel=1e-12, abs=1e-12)


def _scores(o):
    from checks import ring_transition
    return {name: (val, limit) for name, val, limit in
            ring_transition.check(o)}


def _fails(scores):
    return any(val > limit for val, limit in scores.values())


def test_sound_ring_run_passes(ring_run):
    o = ring_run.check_input
    assert (o.num_workers, o.blocks_per_worker) == (4, 2)
    assert not _fails(_scores(o)), _scores(o)
    counts = ring_run.counts
    assert counts["real_tokens"] == counts["iterations"] * o.word.size
    assert counts["slots"] > counts["real_tokens"]
    # what the program says a worker hands on is what the roofline counts
    from harness import common
    assert counts["rotate_bytes"] == common.load_module(
        "roofline", "ring_rotate").bytes_moved(counts)


@pytest.mark.parametrize("rnd", [0, 2])
def test_a_round_left_unsampled_fails(ring_run, rnd):
    o = ring_run.check_input
    rounds = ring.token_rounds(o.word, o.doc, o.vocab_size, 4, 2)
    z = np.where(rounds == rnd, o.z_before, o.z_after)
    scores = _scores(types.SimpleNamespace(**dict(vars(o), z_after=z)))
    assert _fails(scores), scores


def test_scored_against_the_iteration_start_fails(ring_run, monkeypatch):
    """Every round scored against the counts of the iteration's start,
    as the one-round check does."""
    start = ring.round_start_counts

    def stale(*args):
        first = next(start(*args))
        for _ in range(args[-1]):
            yield first
    monkeypatch.setattr(ring, "round_start_counts", stale)
    scores = _scores(ring_run.check_input)
    assert _fails(scores), scores
