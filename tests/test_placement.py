"""Where the engine's arrays live, and what an iteration counts.

On a mesh (``backend="shard_map"``) every layout and state array is split
over the devices by the partition the iteration's ``in_specs`` give it,
before the first step and after a resume, so no device ever stages
another's rows; the uniforms of each iteration are placed the same way,
and the placed chain equals the single-device one bitwise.  The
per-iteration counters (``ModelParallelLDA.counters``) equal hand counts.
"""
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import tracing
from repro.core.engine import ModelParallelLDA

K = 8
LAYOUT = ("doc", "woff", "mask")
ROWS = ("cdk", "ckt", "block_id", "ck_local", "z")


def _assert_placed(lda, spec):
    """Every per-row array split by ``spec`` over ``lda.mesh``, one row
    group a device; ``ck_synced`` whole on every device."""
    n = lda.mesh.size
    arrays = [(name, getattr(lda.layout, name)) for name in LAYOUT]
    arrays += [(name, getattr(lda.state, name)) for name in ROWS]
    arrays.append(("uniforms", lda._uniforms()))
    for name, x in arrays:
        sh = x.sharding
        assert isinstance(sh, NamedSharding), (name, sh)
        assert sh.mesh == lda.mesh and sh.spec == spec, (name, sh)
        shards = x.addressable_shards
        assert len({s.device for s in shards}) == n, name
        assert all(s.data.shape[0] == x.shape[0] // n for s in shards), name
    ck = lda.state.ck_synced.sharding
    assert isinstance(ck, NamedSharding) and ck.spec == P()
    assert len(lda.state.ck_synced.addressable_shards) == n


def _ring(corpus, **kw):
    return ModelParallelLDA(corpus, K, num_workers=4, blocks_per_worker=2,
                            sampler_mode="mh", backend="shard_map", seed=4,
                            **kw)


def _hybrid(corpus, mesh2d, **kw):
    return ModelParallelLDA(corpus, K, num_workers=2, blocks_per_worker=2,
                            data_parallel=2, sampler_mode="mh",
                            backend="shard_map", mesh=mesh2d, axis="model",
                            seed=4, **kw)


def test_ring_arrays_are_on_the_mesh_before_the_first_step(tiny_corpus):
    corpus, _, _ = tiny_corpus
    _assert_placed(_ring(corpus), P("w"))


def test_grid_arrays_are_on_the_mesh_before_the_first_step(tiny_corpus,
                                                           mesh2d):
    corpus, _, _ = tiny_corpus
    _assert_placed(_hybrid(corpus, mesh2d), P(("data", "model")))


@pytest.mark.parametrize("grid", ["1d", "2d"])
def test_resumed_arrays_are_on_the_mesh(tiny_corpus, mesh2d, tmp_path,
                                        grid):
    corpus, _, _ = tiny_corpus
    if grid == "1d":
        lda, kw, spec = _ring(corpus), {}, P("w")
    else:
        lda = _hybrid(corpus, mesh2d)
        kw, spec = dict(mesh=mesh2d, axis="model"), P(("data", "model"))
    lda.step()
    path = lda.save_checkpoint(str(tmp_path / "ck.npz"))
    back = ModelParallelLDA.resume(corpus, path, backend="shard_map", **kw)
    _assert_placed(back, spec)


def test_placed_ring_equals_vmap_and_compiles_once(tiny_corpus):
    """The placed inputs carry the sharding the iteration returns, so the
    second step reuses the first step's program; the chain is the
    single-device chain, bit for bit."""
    corpus, _, _ = tiny_corpus
    ring = _ring(corpus)
    ref = ModelParallelLDA(corpus, K, num_workers=4, blocks_per_worker=2,
                           sampler_mode="mh", seed=4)
    for _ in range(2):
        ring.step()
        ref.step()
    assert ring._iter_fn._cache_size() == 1
    a, b = ring.gather_counts(), ref.gather_counts()
    for name in ("ckt", "cdk", "ck"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)), name)
    np.testing.assert_array_equal(ring.assignments(), ref.assignments())


def test_vmap_arrays_stay_on_one_device(tiny_corpus):
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, K, num_workers=4, blocks_per_worker=2)
    for name in LAYOUT:
        assert len(getattr(lda.layout, name).devices()) == 1, name
    for x in lda.state.tree_flatten()[0]:
        assert len(x.devices()) == 1
    assert lda._uniforms().shape == (8, 4, lda.capacity)


# ---------------------------------------------------------------------------
# Counters
# ---------------------------------------------------------------------------

def _hand_slots(corpus, m, s):
    """``B·R·T`` by hand: documents dealt round robin to ``m`` workers,
    words cut into ``s·m`` equal id ranges, every (worker, block) group
    padded to the largest."""
    b = s * m
    vb = -(-corpus.vocab_size // b)
    worker = corpus.doc % m
    block = corpus.word // vb
    groups = np.bincount(worker * b + block, minlength=m * b)
    return b * m * int(groups.max())


@pytest.mark.parametrize("m,s,lifetime,rotate", [
    # V = 120 over B = 4 blocks: Vb = 30; a block is 4·30·8 = 960 B, its
    # id 4 B, its packed table 3·960 B; one rotation a round, B rounds
    (2, 2, "iteration", 4 * (960 + 4 + 3 * 960)),
    (2, 2, "round", 4 * (960 + 4)),
    # B = 3: Vb = 40, a block 1,280 B
    (3, 1, "iteration", 3 * (1280 + 4 + 3 * 1280)),
    # one worker, one block of all 120 words: 3,840 B
    (1, 1, "iteration", 1 * (3840 + 4 + 3 * 3840)),
])
def test_counters_equal_hand_counts(tiny_corpus, m, s, lifetime, rotate):
    corpus, _, _ = tiny_corpus
    lda = ModelParallelLDA(corpus, K, num_workers=m, blocks_per_worker=s,
                           sampler_mode="mh", table_lifetime=lifetime)
    got = lda.counters()
    assert set(got) == set(tracing.COUNTERS)
    assert got[tracing.SLOTS] == _hand_slots(corpus, m, s)
    assert got[tracing.SLOTS] == lda._uniforms().size
    assert got[tracing.REAL_TOKENS] == corpus.num_tokens == \
        int(np.asarray(lda.mask).sum())
    assert got[tracing.ROTATE_BYTES] == rotate
