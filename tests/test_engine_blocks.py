"""Multi-block pipelined engine (``blocks_per_worker = S``) — the
decoupling of model blocks from workers (DESIGN.md §3).

Covers: (i) S=1 and S=2 bit-equivalence with the host scheduler/KV-store
oracle (the pre-refactor architecture run serially); (ii) vmap vs
shard_map bit-agreement at S ∈ {1, 2} (subprocess, multi-device);
(iii) schedule/count invariants at S ∈ {1, 2, 3} with a vocabulary that
does not divide evenly; (iv) the resident-memory claim — the per-worker
resident block is ``ceil(V/(S·M)) × K`` independent of worker count.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import schedule as sched
from repro.core.counts import build_counts, check_invariants
from repro.core.kvstore import HostModelParallelLDA
from repro.core.engine import ModelParallelLDA
from test_model_parallel import _serial_replay


# ---------------------------------------------------------------------------
# (iii) schedule invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workers", [1, 2, 3, 4, 7])
@pytest.mark.parametrize("s", [1, 2, 3])
def test_pipeline_schedule_is_exact_cover(workers, s):
    """Every round's resident blocks are disjoint; every (worker, block)
    pair meets exactly once per S·M-round iteration."""
    sched.validate_schedule(workers, s)
    table = sched.schedule_table(workers, s)
    assert table.shape == (s * workers, workers)
    # each round: M distinct blocks out of S·M
    for r in range(table.shape[0]):
        assert len(set(table[r])) == workers
    # each worker: all S·M blocks exactly once
    for m in range(workers):
        assert sorted(table[:, m]) == list(range(s * workers))


@pytest.mark.parametrize("workers", [2, 3, 5])
@pytest.mark.parametrize("s", [1, 2, 3])
def test_block_for_reduces_to_paper_rotation_and_inverts(workers, s):
    for r in range(2 * s * workers):
        for w in range(workers):
            b = sched.block_for(w, r, workers, s)
            if s == 1:
                assert b == (w + r) % workers          # paper Algorithm 1
            # resident owner is the inverse on resident rounds
            assert r % s == b // workers
            assert sched.owner_for(b, r, workers, s) == w


def test_rotation_permutation_independent_of_s():
    """Only the resident block travels: the ring permutation is the same
    single-hop m -> m-1 list no matter how many blocks are parked."""
    assert sched.rotation_permutation(4) == [(0, 3), (1, 0), (2, 1), (3, 2)]


# ---------------------------------------------------------------------------
# (iii) engine invariants at S ∈ {1, 2, 3}, non-divisible vocabulary
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workers,s", [(4, 1), (4, 2), (3, 3), (2, 3)])
def test_invariants_and_z_consistency_across_s(tiny_corpus, workers, s):
    corpus, _, _ = tiny_corpus                 # V=120; e.g. B=9 -> Vb=14
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=workers,
                           seed=2, blocks_per_worker=s)
    lda.run(2)
    state = lda.gather_counts()
    check_invariants(state, corpus.num_tokens)
    z = lda.assignments()
    rebuilt = build_counts(corpus.doc, corpus.word, z, corpus.num_docs,
                           corpus.vocab_size, 8)
    np.testing.assert_array_equal(np.asarray(rebuilt.ckt),
                                  np.asarray(state.ckt))
    np.testing.assert_array_equal(np.asarray(rebuilt.cdk),
                                  np.asarray(state.cdk))


@pytest.mark.parametrize("workers,s", [(4, 2), (3, 3)])
def test_parallel_equals_serial_bitexact_pipelined(tiny_corpus, workers, s):
    """The S·M-round pipeline is still exactly equal to its serial replay
    (paper §1's parallel == serial claim survives the generalization)."""
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=workers,
                           seed=11, blocks_per_worker=s)
    rng_state = lda._rng.bit_generator.state
    u = np.asarray(lda._uniforms())
    lda._rng.bit_generator.state = rng_state
    ref_cdk, ref_ckt, ref_ck, ref_z = _serial_replay(lda, u)
    lda.step()
    np.testing.assert_array_equal(np.array(lda.state.cdk), ref_cdk)
    np.testing.assert_array_equal(np.array(lda.state.ckt), ref_ckt)
    np.testing.assert_array_equal(np.array(lda.state.ck_synced), ref_ck)
    np.testing.assert_array_equal(np.array(lda.state.z), ref_z)


def test_likelihood_ascends_with_pipeline(tiny_corpus):
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=4, seed=5,
                           blocks_per_worker=2)
    ll0 = lda.log_likelihood()
    hist = lda.run(6)
    assert hist[-1]["log_likelihood"] > ll0 + 1000


# ---------------------------------------------------------------------------
# (i) bit-equivalence with the host scheduler/KV-store oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s", [1, 2])
def test_engine_equals_host_oracle_bitexact(tiny_corpus, s):
    """The SPMD engine equals the paper's Figure-1 architecture — explicit
    Scheduler / Workers / KV store objects run serially — bit for bit,
    given the same seed (same z0, same uniform stream, same kernel,
    frozen-C_k-per-round semantics)."""
    corpus, _, _ = tiny_corpus
    eng = ModelParallelLDA(corpus, num_topics=8, num_workers=4, seed=7,
                           blocks_per_worker=s)
    host = HostModelParallelLDA(corpus, num_topics=8, num_workers=4,
                                seed=7, blocks_per_worker=s,
                                sampler="scan", ck_sync="round")
    for _ in range(2):
        eng.step()
        host.step()
    np.testing.assert_array_equal(np.asarray(eng.gather_counts().ckt),
                                  host.gather_ckt())
    np.testing.assert_array_equal(eng.assignments(), host.assignments())


# ---------------------------------------------------------------------------
# (iv) resident-memory decoupling — the paper's capacity lever
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workers,s", [(4, 1), (4, 2), (4, 3), (2, 3)])
def test_resident_block_is_v_over_sm(tiny_corpus, workers, s):
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=workers,
                           seed=0, blocks_per_worker=s)
    vb_expected = -(-corpus.vocab_size // (s * workers))   # ceil(V/(S·M))
    assert lda.resident_block_rows == vb_expected
    # the array the engine actually samples each round has exactly that
    # many rows — resident model per worker shrinks with S at fixed M
    assert lda.state.resident_ckt.shape == (workers, vb_expected, 8)
    rep = lda.memory_report()
    assert rep["resident_block_bytes"] == vb_expected * 8 * 4
    assert rep["num_blocks"] == s * workers


def test_backcompat_imports():
    """``repro.core`` exports the engine's own facade and state."""
    from repro.core import ModelParallelLDA as A, MPState as B
    from repro.core.engine import ModelParallelLDA as C, MPState as D
    assert A is C and B is D


# ---------------------------------------------------------------------------
# (ii) vmap vs shard_map agreement at S ∈ {1, 2} (multi-device subprocess)
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.data.synthetic import synthetic_corpus
from repro.core.engine import ModelParallelLDA

corpus, _, _ = synthetic_corpus(num_docs=40, vocab_size=120, num_topics=8,
                                doc_len=30, seed=0)
for s in (1, 2):
    a = ModelParallelLDA(corpus, 8, 4, seed=1, backend="vmap",
                         blocks_per_worker=s)
    b = ModelParallelLDA(corpus, 8, 4, seed=1, backend="shard_map",
                         blocks_per_worker=s)
    for _ in range(2):
        a.step(); b.step()
    sa, sb = a.gather_counts(), b.gather_counts()
    assert (np.asarray(sa.ckt) == np.asarray(sb.ckt)).all(), f"ckt S={s}"
    assert (np.asarray(sa.cdk) == np.asarray(sb.cdk)).all(), f"cdk S={s}"
    assert (a.assignments() == b.assignments()).all(), f"z S={s}"
    assert np.allclose(a.round_errors, b.round_errors, atol=1e-6), \
        f"errs S={s}"
print("PIPELINED_SHARD_MAP_OK")
"""


@pytest.mark.slow
def test_shard_map_equals_vmap_pipelined_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINED_SHARD_MAP_OK" in out.stdout
