"""Serving scheduler (DESIGN.md §14) under a deterministic virtual clock.

No wall-clock sleeps anywhere: every test drives a `VirtualClock`, so
timing-dependent behaviour (batch-delay deadlines, open-loop replay,
latency stamps) is exact and replayable.  The load-bearing property is
the SEED CONTRACT: a response is a pure function of (snapshot contents,
token multiset, scheduler seed), computable standalone by
``reference_theta`` — which turns batching, caching, multi-replica
dispatch, and mid-replay hot-swaps into bitwise-testable refactorings
of the same function.

Layers:

* **batching invariants** — FIFO admission order, batch ≤ capacity,
  no request starves past the configured deadline, partial batches held
  then force-dispatched.
* **admission control** — every rejection path, with reasons.
* **hot swap** — zero dropped, zero epoch-mixed responses across a
  mid-replay swap; every response bitwise equal to serving its request
  against its stamped snapshot alone.
* **cache** — multiset key permutation-invariant and collision-checked,
  hits bitwise equal to fresh fold-ins, LRU eviction, swap invalidation.
"""
import numpy as np
import pytest

from repro.core.infer import ModelSnapshot
from repro.serve.scheduler import (REJECT_BAD_WORD, REJECT_EMPTY,
                                   REJECT_QUEUE_FULL, REJECT_TOO_LONG,
                                   QueryCache, ServingScheduler,
                                   VirtualClock, canonical_tokens,
                                   multiset_digest, reference_theta,
                                   request_draws)
from repro.serve.traffic import poisson_trace, replay_open_loop

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

V, K = 64, 8
SWEEPS = 3
SEED = 1


def _snapshot(seed: int) -> ModelSnapshot:
    rng = np.random.default_rng(seed)
    return ModelSnapshot.from_counts(
        rng.integers(0, 30, size=(V, K)).astype(np.int32))


@pytest.fixture(scope="module")
def snap_a():
    return _snapshot(10)


@pytest.fixture(scope="module")
def snap_b():
    return _snapshot(20)


def _sched(snap, **kw) -> ServingScheduler:
    kw.setdefault("clock", VirtualClock())
    kw.setdefault("sampler", "scan")
    kw.setdefault("num_sweeps", SWEEPS)
    kw.setdefault("seed", SEED)
    return ServingScheduler(snap, **kw)


def _docs(n, seed=0, lo=3, hi=14):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, V, size=int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


def _ref(snap, tokens, sampler="scan"):
    return reference_theta(snap, tokens, sampler=sampler,
                           num_sweeps=SWEEPS, seed=SEED)


# ---------------------------------------------------------------------------
# Clock
# ---------------------------------------------------------------------------

def test_virtual_clock():
    c = VirtualClock(5.0)
    assert c.now() == 5.0
    c.advance(1.5)
    c.sleep(0.5)               # sleep == advance: no wall time anywhere
    assert c.now() == 7.0
    with pytest.raises(ValueError):
        c.advance(-1.0)


# ---------------------------------------------------------------------------
# Batching invariants
# ---------------------------------------------------------------------------

def test_fifo_admission_order(snap_a):
    sched = _sched(snap_a, max_batch=4)
    ids = [sched.submit(d) for d in _docs(10, seed=2)]
    out = sched.tick()
    assert [r.req_id for r in out] == ids            # FIFO, across batches
    assert all(r.status == "ok" for r in out)
    disp = [r.t_dispatch for r in out]
    assert disp == sorted(disp)
    assert sched.pending == 0 and sched.dropped() == 0


def test_batch_never_exceeds_capacity(snap_a):
    sched = _sched(snap_a, max_batch=4)
    for d in _docs(10, seed=3):
        sched.submit(d)
    sched.tick()
    sizes = [b["size"] for b in sched.batch_log]
    assert sizes == [4, 4, 2]                         # FIFO prefix groups
    for b in sched.batch_log:
        assert b["size"] <= 4
        assert b["bucket"][0] <= 4                    # pow2 pad of <= max

def test_partial_batch_held_until_deadline(snap_a):
    clock = VirtualClock()
    sched = _sched(snap_a, max_batch=4, max_batch_delay=1.0, clock=clock)
    sched.submit(_docs(1, seed=4)[0])
    assert sched.tick() == []                 # young partial batch: held
    clock.advance(0.5)
    assert sched.tick() == []
    clock.advance(0.6)                        # age 1.1 >= deadline 1.0
    out = sched.tick()
    assert len(out) == 1
    assert out[0].t_dispatch - out[0].t_arrival == pytest.approx(1.1)


def test_full_batch_dispatches_despite_delay(snap_a):
    sched = _sched(snap_a, max_batch=4, max_batch_delay=100.0)
    for d in _docs(4, seed=5):
        sched.submit(d)
    assert len(sched.tick()) == 4             # full => no reason to wait


def test_flush_dispatches_partial_batch(snap_a):
    sched = _sched(snap_a, max_batch=8, max_batch_delay=100.0)
    sched.submit(_docs(1, seed=6)[0])
    assert sched.tick() == []
    assert len(sched.drain()) == 1


def test_no_request_starves_past_deadline(snap_a):
    """The no-starvation invariant: with ticks every ``dt``, every
    request dispatches within ``max_batch_delay + dt`` of arrival —
    batching can delay a request up to the deadline, never past it."""
    delay, dt = 0.5, 0.2
    clock = VirtualClock()
    sched = _sched(snap_a, max_batch=4, max_batch_delay=delay, clock=clock)
    trace = poisson_trace(30, 50.0, V, seed=7, max_len=12)
    i = 0
    while i < len(trace) or sched.pending:
        now = clock.now()
        while i < len(trace) and trace[i].t <= now:
            sched.submit(trace[i].tokens, now=trace[i].t)
            i += 1
        sched.tick()
        clock.advance(dt)
    waits = [r.t_dispatch - r.t_arrival for r in sched.ok_responses()
             if not r.cached]
    assert len(sched.ok_responses()) == 30 and sched.dropped() == 0
    assert max(waits) <= delay + dt + 1e-9


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------

def test_rejection_paths(snap_a):
    sched = _sched(snap_a, max_queue=2, max_doc_tokens=8)
    r_empty = sched.submit([])
    r_long = sched.submit(np.arange(9))
    r_bad = sched.submit([0, V])                    # id out of vocab
    ok1 = sched.submit([1, 2, 3])
    ok2 = sched.submit([4, 5, 6])
    r_full = sched.submit([7, 8])                   # queue depth 2 hit
    assert sched.results[r_empty].reason == REJECT_EMPTY
    assert sched.results[r_long].reason == REJECT_TOO_LONG
    assert sched.results[r_bad].reason == REJECT_BAD_WORD
    assert sched.results[r_full].reason == REJECT_QUEUE_FULL
    for rid in (r_empty, r_long, r_bad, r_full):
        resp = sched.results[rid]
        assert resp.status == "rejected" and resp.theta is None
        assert resp.t_finish == resp.t_arrival      # rejected instantly
    assert ok1 not in (r_empty, r_long, r_bad) and ok2 != ok1
    assert sched.rejections == {REJECT_EMPTY: 1, REJECT_TOO_LONG: 1,
                                REJECT_BAD_WORD: 1, REJECT_QUEUE_FULL: 1}
    assert sched.admitted == 2 and sched.submitted == 6
    sched.drain()
    assert sched.dropped() == 0                     # rejected != dropped


def test_constructor_validation(snap_a):
    with pytest.raises(ValueError, match="num_replicas"):
        _sched(snap_a, num_replicas=0)
    with pytest.raises(ValueError, match="max_batch"):
        _sched(snap_a, max_batch=0)
    with pytest.raises(ValueError, match="max_queue"):
        _sched(snap_a, max_queue=0)


# ---------------------------------------------------------------------------
# The seed contract: responses are pure functions of the request
# ---------------------------------------------------------------------------

def test_response_independent_of_batch_composition(snap_a):
    """The same doc served alone, batched with strangers, and through a
    different max_batch must produce the SAME bits — the property the
    cache, hot-swap, and replica dispatch all rest on."""
    doc = _docs(1, seed=8)[0]
    ref = _ref(snap_a, doc)
    for kw, extra in [(dict(max_batch=1), 0), (dict(max_batch=8), 5),
                      (dict(max_batch=3, num_replicas=2), 7)]:
        sched = _sched(snap_a, **kw)
        rid = sched.submit(doc)
        for d in _docs(extra, seed=9, lo=2, hi=30):
            sched.submit(d)
        sched.drain()
        np.testing.assert_array_equal(sched.results[rid].theta, ref)


def test_replaying_seeded_trace_twice_is_bitwise_identical(snap_a, snap_b):
    """The acceptance property: same trace, same seed, fresh scheduler
    -> every response identical bit for bit, including timings (virtual
    clock) and swap behaviour."""
    trace = poisson_trace(24, 80.0, V, seed=11, max_len=20,
                          hot_fraction=0.3, hot_pool=3)
    outs = []
    for _ in range(2):
        sched = _sched(snap_a, max_batch=4, num_replicas=2)
        summary = replay_open_loop(sched, trace, swap_after=12,
                                   swap_snapshot=snap_b)
        assert summary["dropped"] == 0
        outs.append(sched)
    a, b = outs
    assert set(a.results) == set(b.results)
    for rid in a.results:
        ra, rb = a.results[rid], b.results[rid]
        assert (ra.status, ra.epoch, ra.fingerprint, ra.replica,
                ra.cached) == (rb.status, rb.epoch, rb.fingerprint,
                               rb.replica, rb.cached)
        assert (ra.t_arrival, ra.t_dispatch, ra.t_finish) == \
            (rb.t_arrival, rb.t_dispatch, rb.t_finish)
        if ra.status == "ok":
            np.testing.assert_array_equal(ra.theta, rb.theta)


def test_round_robin_replica_dispatch(snap_a):
    sched = _sched(snap_a, max_batch=1, num_replicas=3)
    docs = _docs(6, seed=12)
    for d in docs:
        sched.submit(d)
    out = sched.tick()
    assert [r.replica for r in out] == [0, 1, 2, 0, 1, 2]
    # replicas share one snapshot object: derived state built once
    servers = sched._servers[sched.epoch]
    assert all(s.snapshot is sched.snapshot for s in servers)
    # and every replica produces contract bits
    for r, d in zip(out, docs):
        np.testing.assert_array_equal(r.theta, _ref(snap_a, d))


# ---------------------------------------------------------------------------
# Hot swap: zero downtime, zero dropped, zero epoch-mixed
# ---------------------------------------------------------------------------

def test_swap_binds_epoch_at_admission(snap_a, snap_b):
    sched = _sched(snap_a, max_batch=8)
    pre = [sched.submit(d) for d in _docs(3, seed=13)]
    new_epoch = sched.swap_snapshot(snap_b)
    assert new_epoch == 1
    post = [sched.submit(d) for d in _docs(3, seed=14)]
    sched.drain()
    fp_a, fp_b = snap_a.fingerprint(), snap_b.fingerprint()
    for rid in pre:       # admitted before the swap: OLD snapshot
        assert sched.results[rid].epoch == 0
        assert sched.results[rid].fingerprint == fp_a
    for rid in post:      # admitted after: NEW snapshot
        assert sched.results[rid].epoch == 1
        assert sched.results[rid].fingerprint == fp_b
    for b in sched.batch_log:                 # no batch mixes epochs
        assert b["size"] <= 8
    assert [b["epoch"] for b in sched.batch_log] == [0, 1]
    assert sched.dropped() == 0


@pytest.mark.parametrize("sampler", ["scan", "mh"])
def test_mid_replay_swap_bitwise_equivalence(snap_a, snap_b, sampler):
    """THE hot-swap acceptance test: replay a seeded trace with a swap
    at the midpoint; every response must be bitwise equal to serving
    that request ALONE against its stamped snapshot; both epochs serve;
    nothing is dropped; no response mixes epochs."""
    trace = poisson_trace(20, 100.0, V, seed=15, max_len=16,
                          hot_fraction=0.2, hot_pool=3)
    sched = _sched(snap_a, sampler=sampler, max_batch=4, num_replicas=2)
    summary = replay_open_loop(sched, trace, swap_after=10,
                               swap_snapshot=snap_b)
    assert summary["dropped"] == 0
    assert summary["swap_epoch"] == 1
    assert set(summary["epochs"]) == {0, 1}          # both models served
    fp = {0: snap_a.fingerprint(), 1: snap_b.fingerprint()}
    by_snap = {snap_a.fingerprint(): snap_a, snap_b.fingerprint(): snap_b}
    for i, req in enumerate(trace):
        r = sched.results[i]
        assert r.status == "ok"
        # the stamp is self-consistent: epoch <-> fingerprint
        assert r.fingerprint == fp[r.epoch]
        # and truthful: the response IS that snapshot's answer, bitwise
        np.testing.assert_array_equal(
            r.theta, reference_theta(by_snap[r.fingerprint], req.tokens,
                                     sampler=sampler, num_sweeps=SWEEPS,
                                     seed=SEED))
    for b in sched.batch_log:                 # a batch binds ONE snapshot
        assert b["epoch"] in (0, 1)


def test_swap_closes_epoch_group_immediately(snap_a, snap_b):
    """A queued pre-swap group can never grow after the swap, so it
    dispatches at the next tick even if the batch-delay deadline hasn't
    passed — swaps never add latency to old-epoch stragglers."""
    sched = _sched(snap_a, max_batch=8, max_batch_delay=100.0)
    rid = sched.submit(_docs(1, seed=16)[0])
    assert sched.tick() == []                 # held: young partial batch
    sched.swap_snapshot(snap_b)
    out = sched.tick()                        # epoch closed: go now
    assert [r.req_id for r in out] == [rid]
    assert out[0].epoch == 0


def test_swap_releases_old_servers_once_drained(snap_a, snap_b):
    sched = _sched(snap_a)
    sched.submit(_docs(1, seed=17)[0])
    sched.swap_snapshot(snap_b)
    assert set(sched._servers) == {0, 1}      # old epoch still queued
    sched.drain()
    sched.tick()
    assert set(sched._servers) == {1}         # drained -> released
    assert sched.snapshot is snap_b


def test_drained_epoch_frees_its_device_copy():
    """Both snapshots are resident while the old epoch drains; once it
    has, nothing keeps the old device arrays alive, and the answers
    stamped with it are still its snapshot's."""
    import weakref

    import jax
    v_old = 61                                # a shape no other test uses
    rng = np.random.default_rng(30)
    old = ModelSnapshot.from_counts(
        rng.integers(0, 30, size=(v_old, K)).astype(np.int32))
    sched = _sched(old, sampler="sparse", num_replicas=2)
    refs = [weakref.ref(a) for a in old.device_operands("sparse")]
    docs = [d % v_old for d in _docs(3, seed=31)]
    pre = [sched.submit(d) for d in docs]
    new = _snapshot(32)
    sched.swap_snapshot(new)
    assert all(r() is not None for r in refs)     # still queued: resident
    assert new.placements == 1                    # placed at the swap
    post = [sched.submit(d) for d in docs]
    sched.drain()
    assert all(r() is None for r in refs)
    assert not [a for a in jax.live_arrays() if a.shape[:1] == (v_old,)]
    for rid in pre:
        assert sched.results[rid].epoch == 0
    for rid, d in zip(pre, docs):
        np.testing.assert_array_equal(sched.results[rid].theta,
                                      _ref(old, d, sampler="sparse"))
    for rid, d in zip(post, docs):
        assert sched.results[rid].epoch == 1
        np.testing.assert_array_equal(sched.results[rid].theta,
                                      _ref(new, d, sampler="sparse"))


def test_swap_to_identical_snapshot_is_observable(snap_a):
    """Epoch says WHEN, fingerprint says WHAT: swapping in a
    bit-identical model bumps the epoch, keeps the fingerprint, and —
    because draws key on content, not epoch — keeps every response's
    bits."""
    twin = _snapshot(10)                      # same counts as snap_a
    assert twin.fingerprint() == snap_a.fingerprint()
    doc = _docs(1, seed=18)[0]
    sched = _sched(snap_a)
    r0 = sched.submit(doc)
    sched.drain()
    sched.swap_snapshot(twin)
    r1 = sched.submit(doc)
    sched.drain()
    a, b = sched.results[r0], sched.results[r1]
    assert (a.epoch, b.epoch) == (0, 1)
    assert a.fingerprint == b.fingerprint
    assert not b.cached                       # swap cleared the cache...
    np.testing.assert_array_equal(a.theta, b.theta)   # ...same bits anyway


# ---------------------------------------------------------------------------
# Hot-query cache
# ---------------------------------------------------------------------------

def test_cache_hit_bitwise_equals_fresh_fold_in(snap_a):
    doc = _docs(1, seed=19)[0]
    sched = _sched(snap_a)
    r0 = sched.submit(doc)
    sched.drain()
    batches = len(sched.batch_log)
    r1 = sched.submit(doc)                    # same multiset: hot
    a, b = sched.results[r0], sched.results[r1]
    assert not a.cached and b.cached
    assert len(sched.batch_log) == batches    # no fold-in ran
    np.testing.assert_array_equal(b.theta, a.theta)
    np.testing.assert_array_equal(b.theta, _ref(snap_a, doc))
    assert sched.cache_hits == 1


def test_cache_key_is_permutation_invariant(snap_a):
    rng = np.random.default_rng(21)
    doc = rng.integers(0, V, size=12).astype(np.int32)
    sched = _sched(snap_a)
    r0 = sched.submit(doc)
    sched.drain()
    hits = []
    for _ in range(3):
        rid = sched.submit(rng.permutation(doc))
        hits.append(sched.results[rid])
    assert all(h.cached for h in hits)
    for h in hits:
        np.testing.assert_array_equal(h.theta, sched.results[r0].theta)


def test_cache_collision_degrades_to_miss(snap_a, monkeypatch):
    """Force every digest to collide: the stored canonical-array check
    must turn the collision into a MISS (correct answer recomputed),
    never into serving another multiset's response."""
    doc_a, doc_b = _docs(2, seed=22)
    import repro.serve.scheduler as mod
    monkeypatch.setattr(mod, "multiset_digest", lambda canon: b"COLLIDE")
    ref_b = _ref(snap_a, doc_b)     # same patched digest -> same draws
    sched = _sched(snap_a)
    sched.submit(doc_a)
    sched.drain()
    rid = sched.submit(doc_b)                 # same digest, diff multiset
    sched.drain()
    r = sched.results[rid]
    assert not r.cached
    assert sched.cache.collisions >= 1
    np.testing.assert_array_equal(r.theta, ref_b)


def test_cache_lru_eviction_respects_capacity(snap_a):
    docs = _docs(3, seed=23)
    sched = _sched(snap_a, cache_capacity=2)
    for d in docs:                            # A, B, C -> A evicted
        sched.submit(d)
        sched.drain()
    assert len(sched.cache) == 2
    assert sched.cache.evictions == 1
    rid = sched.submit(docs[0])               # A: miss, recomputed
    sched.drain()
    assert not sched.results[rid].cached
    # hit refreshes recency: touch A (now resident), add D -> C evicted
    assert sched.results[sched.submit(docs[0])].cached
    sched.submit(_docs(1, seed=24)[0])
    sched.drain()
    assert sched.results[sched.submit(docs[0])].cached      # A survived
    rid_c = sched.submit(docs[2])                           # C evicted:
    sched.drain()                                           # miss, requeued
    assert not sched.results[rid_c].cached


def test_cache_disabled_at_zero_capacity(snap_a):
    doc = _docs(1, seed=25)[0]
    sched = _sched(snap_a, cache_capacity=0)
    sched.submit(doc)
    sched.drain()
    rid = sched.submit(doc)
    sched.drain()
    assert not sched.results[rid].cached
    assert len(sched.cache) == 0


def test_swap_invalidates_cache(snap_a, snap_b):
    doc = _docs(1, seed=26)[0]
    sched = _sched(snap_a)
    sched.submit(doc)
    sched.drain()
    assert len(sched.cache) == 1
    sched.swap_snapshot(snap_b)
    assert len(sched.cache) == 0
    rid = sched.submit(doc)
    sched.drain()
    r = sched.results[rid]
    assert not r.cached and r.fingerprint == snap_b.fingerprint()
    np.testing.assert_array_equal(r.theta, _ref(snap_b, doc))


def test_cache_hit_bypasses_full_queue(snap_a):
    """Hot queries cost no queue slot, so overload shedding never sheds
    traffic the cache has already paid for."""
    hot = _docs(1, seed=27)[0]
    sched = _sched(snap_a, max_queue=1)
    sched.submit(hot)
    sched.drain()
    sched.submit(_docs(1, seed=28)[0])        # occupies the only slot
    rid_hot = sched.submit(hot)               # still served, instantly
    rid_cold = sched.submit(_docs(1, seed=29)[0])
    assert sched.results[rid_hot].cached
    assert sched.results[rid_cold].reason == REJECT_QUEUE_FULL


def test_query_cache_unit():
    cache = QueryCache(capacity=1)
    canon = canonical_tokens([3, 1, 2])
    np.testing.assert_array_equal(canon, [1, 2, 3])
    d = multiset_digest(canon)
    assert d == multiset_digest(canonical_tokens([2, 3, 1]))
    assert cache.get(d, canon) is None
    cache.put(d, canon, np.arange(3.0))
    np.testing.assert_array_equal(cache.get(d, canon), np.arange(3.0))
    assert (cache.hits, cache.misses) == (1, 1)


if HAVE_HYPOTHESIS:
    @given(st.lists(st.integers(0, V - 1), min_size=1, max_size=24),
           st.randoms(use_true_random=False))
    @settings(max_examples=25, deadline=None)
    def test_multiset_key_permutation_property(tokens, pyrandom):
        """Hypothesis: ANY permutation of ANY doc produces the same
        canonical form, digest, and per-request draws — the cache-key
        contract, independent of the fold-in."""
        shuffled = list(tokens)
        pyrandom.shuffle(shuffled)
        c0, c1 = canonical_tokens(tokens), canonical_tokens(shuffled)
        np.testing.assert_array_equal(c0, c1)
        assert multiset_digest(c0) == multiset_digest(c1)
        z0a, ua = request_draws(SEED, "ab12", multiset_digest(c0),
                                c0.size, K, SWEEPS)
        z0b, ub = request_draws(SEED, "ab12", multiset_digest(c1),
                                c1.size, K, SWEEPS)
        np.testing.assert_array_equal(z0a, z0b)
        np.testing.assert_array_equal(ua, ub)


# ---------------------------------------------------------------------------
# Observability / stats
# ---------------------------------------------------------------------------

def test_stats_and_latency_summary(snap_a, snap_b):
    clock = VirtualClock()
    sched = _sched(snap_a, max_batch=4, clock=clock)
    trace = poisson_trace(16, 60.0, V, seed=30, max_len=12,
                          hot_fraction=0.4, hot_pool=2)
    replay_open_loop(sched, trace, swap_after=8, swap_snapshot=snap_b)
    s = sched.stats()
    assert s["submitted"] == 16 and s["dropped"] == 0
    assert s["served"] == s["admitted"] == 16
    assert s["swaps"] == 1 and s["epoch"] == 1
    assert s["cache"]["hits"] == sched.cache_hits
    lat = sched.latency_summary()
    assert lat["served"] == 16
    assert np.isfinite(lat["p50_ms"]) and np.isfinite(lat["p99_ms"])
    assert lat["p50_ms"] <= lat["p99_ms"]
    # virtual clock: fold-ins are instant, so latency is pure queueing
    for r in sched.ok_responses():
        assert r.t_arrival <= r.t_dispatch <= r.t_finish


# ---------------------------------------------------------------------------
# lda_serve snapshot watcher (unit: no subprocess, no wall clock)
# ---------------------------------------------------------------------------

def test_lda_serve_watcher_swaps_on_new_snapshot(tmp_path, snap_a, snap_b):
    import argparse
    import os

    from repro.launch.lda_serve import _make_watcher
    a_path, b_path = str(tmp_path / "a.npz"), str(tmp_path / "b.npz")
    snap_a.save(a_path)
    os.utime(a_path, (1000.0, 1000.0))
    args = argparse.Namespace(snapshot=a_path, watch=str(tmp_path),
                              watch_interval=0.0)
    sched = _sched(snap_a)
    on_tick = _make_watcher(args, sched)
    on_tick(sched, 0.0)
    assert sched.epoch == 0                   # nothing new yet
    snap_b.save(b_path)
    os.utime(b_path, (2000.0, 2000.0))        # strictly newer
    on_tick(sched, 1.0)
    assert sched.epoch == 1
    assert sched.fingerprint == snap_b.fingerprint()
    on_tick(sched, 2.0)                       # same file: no re-swap
    assert sched.epoch == 1
