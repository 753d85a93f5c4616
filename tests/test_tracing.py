"""The program's own tracing (``repro/tracing.py``): device scopes in the
training iteration's op metadata, the fold-in's host->device byte
counter, the scheduler's per-batch counters, and host spans in a real
profiler trace under bare names."""
import glob
import re
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core.engine import ModelParallelLDA
from repro.core.engine.backends import iteration_vmap
from repro.core.faults import FaultPlan
from repro.core.infer import ModelSnapshot, fold_in, pack_queries
from repro.serve.scheduler import (BATCH_LOG_LEN, ServingScheduler,
                                   VirtualClock)

K = 8
STAGES = (tracing.SAMPLE, tracing.ROTATE, tracing.CK_SYNC, tracing.QUEUE)
TABLES = (tracing.WORD_TABLES, tracing.DOC_TABLES)


def _scopes(lowered) -> set:
    """The ``lda.*`` scopes named in a lowered program's op metadata."""
    text = lowered.as_text(dialect="hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', text)
    return {m for n in names for m in re.findall(r"lda\.\w+", n)}


def _lower_step(lda: ModelParallelLDA):
    """Lower the iteration ``lda.step`` would run, without running it."""
    u = lda._uniforms()
    beta, vbeta = jnp.float32(lda.beta), jnp.float32(lda.vbeta)
    if lda.backend == "vmap":
        return iteration_vmap.lower(
            lda.state, u, lda.doc, lda.woff, lda.mask, lda.alpha, beta,
            vbeta, sampler_mode=lda.sampler_mode, sync_ck=lda.sync_ck,
            data_parallel=lda.data_parallel,
            table_lifetime=lda.table_lifetime, track_error=lda.track_error,
            sampler_args=lda.sampler_args)
    s = lda.state
    return lda._iter_fn.lower(
        s.cdk, s.ckt, s.block_id, s.ck_synced, s.ck_local, s.z,
        jnp.swapaxes(u, 0, 1), lda.doc, lda.woff, lda.mask, lda.alpha,
        beta, vbeta)


@pytest.mark.parametrize("lifetime", ["round", "iteration"])
def test_vmap_iteration_names_its_stages(tiny_corpus, lifetime):
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, K, num_workers=2, blocks_per_worker=2,
                           data_parallel=2, sampler_mode="mh",
                           table_lifetime=lifetime)
    got = _scopes(_lower_step(lda))
    assert set(STAGES) | {tracing.RECONCILE} <= got
    if lifetime == "iteration":
        assert set(TABLES) <= got
    else:
        # the round lifetime builds both tables inside the sampler call,
        # in one concatenated build, under lda.sample
        assert not set(TABLES) & got
    assert got <= set(tracing.SCOPES)


@pytest.mark.parametrize("lifetime", ["round", "iteration"])
def test_shard_map_iteration_names_its_stages(tiny_corpus, mesh2d,
                                              lifetime):
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, K, num_workers=2, blocks_per_worker=2,
                           data_parallel=2, sampler_mode="mh",
                           table_lifetime=lifetime, backend="shard_map",
                           mesh=mesh2d, axis="model")
    got = _scopes(_lower_step(lda))
    assert set(STAGES) | {tracing.RECONCILE} <= got
    if lifetime == "iteration":
        assert set(TABLES) <= got
    assert got <= set(tracing.SCOPES)


def test_scopes_keep_the_names_readers_match(tiny_corpus):
    """The scopes nest around the jitted builders; their own names stay in
    the name stack, under the new scope."""
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, K, num_workers=1, sampler_mode="mh",
                           table_lifetime="iteration")
    text = _lower_step(lda).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(f"{tracing.WORD_TABLES}/" in n and "jit(build_word_tables)"
               in n for n in names)
    assert any(f"{tracing.DOC_TABLES}/" in n and "jit(build_doc_tables)"
               in n for n in names)


# ---------------------------------------------------------------------------
# fold_in's host -> device byte counter
# ---------------------------------------------------------------------------

V = 64
SWEEPS = 3


@pytest.fixture(scope="module")
def snap():
    rng = np.random.default_rng(3)
    s = ModelSnapshot.from_counts(
        rng.integers(0, 30, size=(V, K)).astype(np.int32))
    s.ensure_tables()
    s.sparse_state()
    return s


def _batch():
    """Three queries packed into a (4, 16) bucket."""
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, V, size=n).astype(np.int32) for n in (5, 9, 3)]
    return pack_queries(docs, t_pad=16, q_pad=4)


def _query_bytes(word):
    q, t = word.shape
    # cdk0 [Q, K] i32, word and z0 [Q, T] i32, mask [Q, T] bool,
    # u [S, Q, T] f32
    return q * K * 4 + q * t * (4 + 4 + 1) + SWEEPS * q * t * 4


@pytest.mark.parametrize("sampler,model_bytes", [
    ("scan", V * K * 4 + K * 4),                   # word term, alpha
    ("sparse", 2 * V * K * 4 + V * 4),             # word term, Xcs, sX
    # counts, C_k, packed tables [3, V, K], alpha, beta, Vbeta
    ("mh", V * K * 4 + K * 4 + 3 * V * K * 4 + K * 4 + 4 + 4),
])
def test_fold_in_counts_its_host_inputs(snap, sampler, model_bytes):
    word, mask = _batch()
    res = fold_in(snap, word, mask, num_sweeps=SWEEPS, sampler=sampler,
                  seed=1)
    assert res.h2d_bytes == model_bytes + _query_bytes(word)


def test_fold_in_counts_device_arrays_as_zero(snap):
    word, mask = _batch()
    on_host = fold_in(snap, word, mask, num_sweeps=SWEEPS, seed=1)
    resident = ModelSnapshot.from_counts(snap.ckt)
    resident._word_term = jnp.asarray(snap.word_term())
    on_device = fold_in(resident, word, mask, num_sweeps=SWEEPS, seed=1)
    assert on_host.h2d_bytes - on_device.h2d_bytes == V * K * 4
    np.testing.assert_array_equal(on_host.cdk, on_device.cdk)


# ---------------------------------------------------------------------------
# The scheduler's per-batch counters
# ---------------------------------------------------------------------------

def _docs(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, V, size=n).astype(np.int32) for n in lengths]


def test_batch_log_counters_are_exact(snap):
    slow = 0.25
    sched = ServingScheduler(
        snap, sampler="sparse", num_sweeps=SWEEPS, seed=1, max_batch=4,
        clock=VirtualClock(10.0), fault_plan=FaultPlan.replica_slow(0, slow))
    plan = [((5, 9, 3), (10.0, 10.1, 10.3), 10.5, (4, 16)),
            ((12, 2), (11.0, 11.0), 11.25, (2, 16))]
    for i, (lengths, arrivals, t_dispatch, bucket) in enumerate(plan):
        for toks, t in zip(_docs(lengths, seed=i), arrivals):
            sched.submit(toks, now=t)
        sched.clock.advance(t_dispatch - sched.clock.now())
        assert len(sched.tick()) == len(lengths)
        entry = sched.batch_log[-1]
        qb, tb = entry["bucket"]
        assert (qb, tb) == bucket
        assert entry["size"] == len(lengths)
        assert entry["t_dispatch"] == t_dispatch
        assert entry["wait_s"] == sum(t_dispatch - t for t in arrivals)
        assert entry["t_finish"] == t_dispatch + slow
        q = qb * K * 4 + qb * tb * (4 + 4 + 1) + SWEEPS * qb * tb * 4
        assert entry["h2d_bytes"] == 2 * V * K * 4 + V * 4 + q
    assert sched.stats()["batches"] == 2


def test_batch_log_is_a_bounded_ring(snap):
    sched = ServingScheduler(snap, sampler="scan", num_sweeps=SWEEPS,
                             clock=VirtualClock())
    assert sched.batch_log.maxlen == BATCH_LOG_LEN >= 65536
    sched.batch_log = deque(maxlen=2)
    for toks in _docs((4, 5, 6)):
        sched.submit(toks)
        sched.tick()
    assert [b["size"] for b in sched.batch_log] == [1, 1]
    assert sched.stats()["batches"] == 3


# ---------------------------------------------------------------------------
# Host spans in a profiler trace
# ---------------------------------------------------------------------------

FOLDIN = (tracing.FOLDIN_PACK, tracing.FOLDIN_UPLOAD, tracing.FOLDIN_RUN,
          tracing.FOLDIN_FETCH, tracing.FOLDIN_THETA)


def test_one_tick_traces_nested_spans_under_bare_names(snap, tmp_path):
    from jax.profiler import ProfileData
    sched = ServingScheduler(snap, sampler="sparse", num_sweeps=SWEEPS,
                             clock=VirtualClock())
    sched.warm(16)
    for toks in _docs((5, 9)):
        sched.submit(toks)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        sched.tick()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    lines = [ln for p in pd.planes if p.name == "/host:CPU"
             for ln in p.lines
             if any(e.name == tracing.SERVE_BATCH for e in ln.events)]
    assert len(lines) == 1
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns, e)
              for e in lines[0].events]
    batch = [ev for ev in events if ev[0] == tracing.SERVE_BATCH]
    assert len(batch) == 1
    _, lo, hi, ev = batch[0]
    stats = dict(ev.stats)
    assert stats["size"] == 2 and stats["replica"] == 0
    inside = {n for n, s, e, _ in events if lo <= s and e <= hi}
    assert set(FOLDIN) | {tracing.SERVE_DRAWS} <= inside
    # arguments are stats, never part of a name
    assert not any("#" in n or "=" in n for n, *_ in events
                   if n.split(".")[0] in ("serve", "foldin"))
