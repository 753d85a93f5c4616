"""Hybrid data×model parallelism (DESIGN.md §8) — bit-exactness harness.

The 2D ``(data, model)`` engine makes three equivalence claims, each
enforced here bitwise (not statistically):

(i)   **D = 1 is the old engine.**  With ``data_parallel=1`` both backends
      must reproduce the FROZEN pre-2D implementation
      (``core/engine/reference.py``) array-for-array — the 2D
      generalization is not allowed to perturb the 1D semantics.
(ii)  **D > 1 is the serial KV-store architecture.**  With per-round
      reconciliation (``ck_sync="round"``) the engine equals the host
      Scheduler/Workers/KV-store oracle replayed with the same uniform
      stream, for D ∈ {2, 4} and S ∈ {1, 2}.
(iii) **The backends agree.**  vmap and shard_map produce identical
      states on the 2×2 (data, model) mesh (faked devices, main suite —
      no subprocess needed thanks to the conftest XLA flag).

Plus the structural invariants: gathered counts rebuild from assignments
at any (D, M, S), and the degenerate geometries collapse to the expected
algorithms (D=1 → 1D ring, M=1 → AD-LDA).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import schedule as sched
from repro.core.counts import build_counts, check_invariants
from repro.core.data_parallel import adlda_engine
from repro.core.engine import reference
from repro.core.kvstore import HostModelParallelLDA
from repro.core.engine import ModelParallelLDA

STATE_FIELDS = ("cdk", "ckt", "block_id", "ck_synced", "ck_local", "z")


def _assert_states_equal(a, b, ctx=""):
    for f in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{ctx}: state.{f} diverged")


# ---------------------------------------------------------------------------
# (i) D = 1 equals the frozen 1D engine — vmap backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s", [1, 2])
def test_d1_vmap_equals_frozen_1d_reference(tiny_corpus, s):
    """The generalized iteration at ``data_parallel=1`` reproduces the
    pre-2D vmap implementation bit for bit (including the per-round
    Fig-3 error series)."""
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=4, seed=13,
                           blocks_per_worker=s)
    ref = ModelParallelLDA(corpus, num_topics=8, num_workers=4, seed=13,
                           blocks_per_worker=s)
    for _ in range(2):
        lda.step()
        u = ref._uniforms()          # same rng stream as lda's step
        ref.state, errs = reference.iteration_vmap_1d(
            ref.state, u, ref.doc, ref.woff, ref.mask, ref.alpha,
            jnp.float32(ref.beta), jnp.float32(ref.vbeta))
    _assert_states_equal(lda.state, ref.state, f"vmap D=1 S={s}")
    np.testing.assert_allclose(lda.round_errors,
                               np.asarray(errs).reshape(-1), rtol=1e-6)


# ---------------------------------------------------------------------------
# (i) D = 1 equals the frozen 1D engine — shard_map backend (4 devices)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s", [1, 2])
def test_d1_shard_map_2d_path_equals_frozen_1d(tiny_corpus, mesh1x4, s):
    """The 2D shard_map code path on a (1, 4) mesh — psum over a size-1
    data axis — equals the frozen 1D shard_map implementation run on the
    plain 4-worker ring."""
    import jax
    from jax.sharding import Mesh

    corpus, _, _ = tiny_corpus
    two_d = ModelParallelLDA(corpus, num_topics=8, num_workers=4, seed=5,
                             blocks_per_worker=s, backend="shard_map",
                             mesh=mesh1x4, axis="model")
    ref = ModelParallelLDA(corpus, num_topics=8, num_workers=4, seed=5,
                           blocks_per_worker=s)   # state + rng source
    ring = Mesh(np.array(jax.devices()[:4]), ("w",))
    ref_fn = reference.make_shard_map_iteration_1d(ring, "w", "scan", True)
    for _ in range(2):
        two_d.step()
        s_ = ref.state
        u = ref._uniforms()
        out = ref_fn(s_.cdk, s_.ckt, s_.block_id, s_.ck_synced,
                     s_.ck_local, s_.z, jnp.swapaxes(u, 0, 1), ref.doc,
                     ref.woff, ref.mask, ref.alpha,
                     jnp.float32(ref.beta), jnp.float32(ref.vbeta))
        ref.state = type(s_)(*out[:6])
        errs = out[6]
    _assert_states_equal(two_d.state, ref.state, f"shard_map D=1 S={s}")
    np.testing.assert_allclose(two_d.round_errors,
                               np.asarray(errs).reshape(-1), atol=1e-6)


# ---------------------------------------------------------------------------
# (ii) D > 1 with round-sync equals the host KV-store oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,m,s", [(2, 2, 1), (2, 2, 2), (4, 2, 1),
                                   (2, 4, 1)])
def test_hybrid_engine_equals_host_oracle_bitexact(tiny_corpus, d, m, s):
    """The 2D engine equals the paper's Figure-1 architecture extended
    with D doc replicas — same uniforms, same kernel, same frozen-per-round
    staleness model — bit for bit: word-topic table, doc-topic shards,
    and every assignment."""
    corpus, _, _ = tiny_corpus
    eng = ModelParallelLDA(corpus, num_topics=8, num_workers=m, seed=7,
                           blocks_per_worker=s, data_parallel=d)
    host = HostModelParallelLDA(corpus, num_topics=8, num_workers=m,
                                seed=7, blocks_per_worker=s,
                                sampler="scan", ck_sync="round",
                                data_parallel=d)
    for _ in range(2):
        eng.step()
        host.step()
    np.testing.assert_array_equal(np.asarray(eng.gather_counts().ckt),
                                  host.gather_ckt())
    np.testing.assert_array_equal(eng.assignments(), host.assignments())
    np.testing.assert_array_equal(
        np.asarray(eng.state.cdk),
        np.stack([w.cdk for w in host.workers]))


# ---------------------------------------------------------------------------
# (iii) vmap == shard_map on the 2×2 (data, model) mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s", [1, 2])
def test_hybrid_shard_map_equals_vmap(tiny_corpus, mesh2d, s):
    corpus, _, _ = tiny_corpus
    a = ModelParallelLDA(corpus, num_topics=8, num_workers=2, seed=1,
                         data_parallel=2, blocks_per_worker=s)
    b = ModelParallelLDA(corpus, num_topics=8, num_workers=2, seed=1,
                         data_parallel=2, blocks_per_worker=s,
                         backend="shard_map", mesh=mesh2d, axis="model")
    for _ in range(2):
        a.step()
        b.step()
    _assert_states_equal(a.state, b.state, f"2D vmap vs shard_map S={s}")
    np.testing.assert_allclose(a.round_errors, b.round_errors, atol=1e-6)


# ---------------------------------------------------------------------------
# invariants and degenerate geometries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,m,s", [(2, 2, 1), (2, 3, 2), (4, 1, 1),
                                   (3, 2, 2)])
def test_hybrid_invariants_and_z_consistency(tiny_corpus, d, m, s):
    """Gathered counts at any grid geometry rebuild exactly from the
    gathered assignments — replica copies cannot silently diverge, since
    gather reads replica 0's blocks but EVERY replica's assignments."""
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=m, seed=2,
                           blocks_per_worker=s, data_parallel=d)
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


def test_replica_block_copies_identical_at_boundaries(tiny_corpus):
    """The delta psum keeps all D copies of every block slot bitwise equal
    at iteration boundaries (the §8 invariant that makes replica 0 'the'
    model)."""
    corpus, _, _ = tiny_corpus
    d, m = 2, 2
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=m, seed=4,
                           blocks_per_worker=2, data_parallel=d)
    lda.run(2)
    ckt = np.asarray(lda.state.ckt).reshape(d, m, *lda.state.ckt.shape[1:])
    bid = np.asarray(lda.state.block_id).reshape(d, m, -1)
    for rep in range(1, d):
        np.testing.assert_array_equal(ckt[rep], ckt[0])
        np.testing.assert_array_equal(bid[rep], bid[0])


def test_m1_degenerates_to_adlda(tiny_corpus):
    """M=1, S=1: one vocabulary block, ONE round per iteration, every
    replica holds the full table — the engine IS AD-LDA with one
    reconciliation per iteration, and its pre-sync delta error is positive
    like the DP baseline's (the staleness the paper plots in Fig 3)."""
    corpus, _, _ = tiny_corpus
    lda = adlda_engine(corpus, num_topics=8, num_replicas=4, seed=9)
    assert lda.num_rounds == 1
    assert lda.num_blocks == 1
    # full table resident on every replica: the DP memory layout
    assert lda.resident_block_rows >= corpus.vocab_size
    lda.run(2)
    assert lda.delta_error() > 0
    check_invariants(lda.gather_counts(), corpus.num_tokens)


def test_hybrid_likelihood_ascends(tiny_corpus):
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=2, seed=5,
                           data_parallel=2, blocks_per_worker=2)
    ll0 = lda.log_likelihood()
    hist = lda.run(6)
    assert hist[-1]["log_likelihood"] > ll0 + 1000


def test_hybrid_memory_report(tiny_corpus):
    """The two levers are orthogonal: resident block = ceil(V/(S·M))×K
    regardless of D; distributed model bytes scale with D."""
    corpus, _, _ = tiny_corpus
    k = 8
    rep1 = ModelParallelLDA(corpus, k, 2, blocks_per_worker=2).memory_report()
    rep2 = ModelParallelLDA(corpus, k, 2, blocks_per_worker=2,
                            data_parallel=3).memory_report()
    assert rep1["resident_block_bytes"] == rep2["resident_block_bytes"]
    assert rep2["num_shards"] == 6
    assert rep2["distributed_model_bytes"] == 3 * rep2["replica_model_bytes"]
    vb = -(-corpus.vocab_size // 4)
    assert rep2["resident_block_shape"] == (vb, k)


def test_hybrid_constructor_rejects_ill_formed_configs(tiny_corpus, mesh2d):
    """Undefined or silently-corrupting configurations fail at
    construction: sync_ck=False at D>1 (no well-defined replica
    semantics — the host oracle rejects it too) and meshes whose axes
    don't match the (D, M) grid (rows would be silently dropped)."""
    corpus, _, _ = tiny_corpus
    with pytest.raises(ValueError, match="sync_ck"):
        ModelParallelLDA(corpus, num_topics=8, num_workers=2,
                         data_parallel=2, sync_ck=False)
    with pytest.raises(ValueError, match="data_parallel"):
        ModelParallelLDA(corpus, num_topics=8, num_workers=2,
                         data_parallel=0)
    with pytest.raises(ValueError, match="mesh axes"):
        # R = 4·2 = 8 rows cannot live on a 2×2 mesh
        ModelParallelLDA(corpus, num_topics=8, num_workers=2,
                         data_parallel=4, backend="shard_map",
                         mesh=mesh2d, axis="model")
    with pytest.raises(ValueError, match="mesh axes"):
        # D = 2 with a mesh that lacks the data axis entirely
        import jax
        from jax.sharding import Mesh
        ring = Mesh(np.array(jax.devices()[:4]), ("w",))
        ModelParallelLDA(corpus, num_topics=8, num_workers=2,
                         data_parallel=2, backend="shard_map", mesh=ring)


def test_hybrid_uses_2d_schedule_table(tiny_corpus):
    """The engine's per-round resident blocks follow schedule_table_2d:
    aligned across replicas, disjoint along model."""
    corpus, _, _ = tiny_corpus
    d, m, s = 2, 2, 2
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=m, seed=0,
                           blocks_per_worker=s, data_parallel=d)
    table = sched.schedule_table_2d(d, m, s)
    res = np.asarray(lda.state.block_id)[:, 0].reshape(d, m)
    np.testing.assert_array_equal(res, table[0])
