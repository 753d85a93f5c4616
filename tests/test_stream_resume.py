"""Out-of-core streaming + bit-exact checkpoint/resume (DESIGN.md §13).

Three pillars, each proven bitwise:

1. **The rng stream splits.**  The streaming engine draws its uniforms in
   per-round chunks and its initial assignments in per-shard chunks, and
   the resume path serializes the generator through JSON.  All three
   lean on numpy Generator properties that are pinned here so a numpy
   upgrade that silently changes them fails THESE tests, not a 2-hour
   training run.
2. **Streaming == in-memory.**  `StreamingLDA` — one resident ``[Vb, K]``
   block, per-(row, block) state loaded from disk on demand — produces
   the identical chain to `ModelParallelLDA` holding everything in RAM:
   same counts, same ``C_k``, same assignments, across samplers and
   (S, D) geometries.
3. **Resume == uninterrupted.**  A run killed at an iteration boundary
   and resumed from its checkpoint is draw-for-draw the run that never
   stopped — for the streaming engine, the device engine on BOTH
   backends (including resuming a vmap checkpoint on shard_map and vice
   versa — checkpoints carry no backend state), and the host KV-store
   oracle; and the resumed engine still replays the resumed oracle.

Plus the satellite regime-map decision table (``--sampler auto``) and
the row-restricted sharded-snapshot serving path
(`load_snapshot_rows`), which must fold in bitwise-equal to the full
snapshot.
"""
import json
import os

import numpy as np
import pytest

from repro.core.infer import (fold_in, load_sharded_snapshot_meta,
                              load_snapshot_rows, pack_queries)
from repro.core.kvstore import HostModelParallelLDA
from repro.core.engine import ModelParallelLDA
from repro.data.stream import ShardedCorpus, shard_corpus
from repro.launch.samplers import (REGIME_MAP, regime_sampler,
                                   resolve_sampler_choice)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def _assert_chains_equal(a, b, ctx: str):
    """Full observable chain state: counts, C_k, and every assignment."""
    sa, sb = a.gather_counts(), b.gather_counts()
    np.testing.assert_array_equal(np.asarray(sa.ckt), np.asarray(sb.ckt),
                                  err_msg=f"{ctx}: ckt diverged")
    np.testing.assert_array_equal(np.asarray(sa.cdk), np.asarray(sb.cdk),
                                  err_msg=f"{ctx}: cdk diverged")
    np.testing.assert_array_equal(np.asarray(sa.ck), np.asarray(sb.ck),
                                  err_msg=f"{ctx}: ck diverged")
    np.testing.assert_array_equal(a.assignments(), b.assignments(),
                                  err_msg=f"{ctx}: z diverged")


# ---------------------------------------------------------------------------
# (1) numpy Generator contracts the streaming/resume design relies on
# ---------------------------------------------------------------------------

def test_rng_integers_chunked_equals_one_shot():
    """Drawing N ints in sequential chunks equals one N-draw — the
    streaming init draws z0 per corpus shard and must match the
    in-memory engine's single draw over the whole token stream."""
    one = np.random.default_rng(42).integers(0, 50, size=100)
    rng = np.random.default_rng(42)
    parts = [rng.integers(0, 50, size=n) for n in (10, 25, 65)]
    np.testing.assert_array_equal(np.concatenate(parts), one)


def test_rng_random_c_order_chunking():
    """A ``[B, R, cap]`` float32 draw equals B sequential ``[R, cap]``
    draws — the streaming engine draws uniforms per ROUND while the
    in-memory engine draws the whole iteration at once."""
    one = np.random.default_rng(7).random((3, 4, 5), dtype=np.float32)
    rng = np.random.default_rng(7)
    parts = [rng.random((4, 5), dtype=np.float32) for _ in range(3)]
    np.testing.assert_array_equal(np.stack(parts), one)


def test_rng_bitgen_state_json_roundtrip():
    """``bit_generator.state`` survives a JSON round trip (PCG64's
    128-bit integers are Python ints) and restores the exact stream —
    the checkpoint serializes the generator this way."""
    rng = np.random.default_rng(123)
    rng.random(17)                      # advance off the seed point
    rng.integers(0, 9, 5)
    state = json.loads(json.dumps(rng.bit_generator.state))
    fresh = np.random.default_rng()
    fresh.bit_generator.state = state
    np.testing.assert_array_equal(fresh.random(8), rng.random(8))
    np.testing.assert_array_equal(fresh.integers(0, 99, 8),
                                  rng.integers(0, 99, 8))


# ---------------------------------------------------------------------------
# (2) streaming == in-memory, across samplers and geometries
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_sharded(tiny_corpus, tmp_path_factory):
    """The tiny corpus, sharded on disk (3 shards) next to its in-memory
    twin."""
    corpus, _, _ = tiny_corpus
    out = str(tmp_path_factory.mktemp("sharded") / "corpus")
    shard_corpus(corpus, out, num_shards=3)
    return corpus, ShardedCorpus(out)


def test_shard_roundtrip_preserves_stream(tiny_sharded):
    """Sharding is a pure re-layout: concatenating the shards (and
    ``to_corpus``) reproduces the original token stream exactly."""
    corpus, sc = tiny_sharded
    back = sc.to_corpus()
    np.testing.assert_array_equal(back.doc, corpus.doc)
    np.testing.assert_array_equal(back.word, corpus.word)
    cat = np.concatenate([s.word for s in sc.iter_shards()])
    np.testing.assert_array_equal(cat, corpus.word)
    assert sc.max_doc_len == int(corpus.doc_lengths().max())


@pytest.mark.parametrize("mode,m,s,d", [
    ("scan", 2, 1, 1),
    ("mh", 2, 2, 2),       # traveling tables + pipelining + replicas
    ("sparse", 3, 1, 1),
])
def test_streaming_equals_in_memory(tiny_sharded, tmp_path, mode, m, s, d):
    from repro.core.engine.streaming import StreamingLDA
    corpus, sc = tiny_sharded
    mem = ModelParallelLDA(corpus, num_topics=8, num_workers=m, seed=11,
                           sampler_mode=mode, blocks_per_worker=s,
                           data_parallel=d)
    disk = StreamingLDA(sc, str(tmp_path / "run"), num_topics=8,
                        num_workers=m, seed=11, sampler_mode=mode,
                        blocks_per_worker=s, data_parallel=d)
    for _ in range(2):
        mem.step()
        disk.step()
    _assert_chains_equal(mem, disk, f"stream vs mem {mode} S={s} D={d}")
    # the resident set really is one block: [Vb, K] of the full [V, K]
    # (>= because the partition pads V up to a multiple of the blocks)
    rep = disk.memory_report()
    assert rep["resident_block_bytes"] * disk.num_blocks \
        >= rep["total_model_bytes"]
    assert rep["resident_block_bytes"] < rep["total_model_bytes"]


def test_streaming_resume_equals_uninterrupted(tiny_sharded, tmp_path):
    """Kill-at-boundary semantics: checkpoint at iter 2, keep training
    (dirtying the live state), then resume from the checkpoint and run
    to iter 4 — identical to the run that never stopped, including the
    restored rng stream."""
    from repro.core.engine.streaming import StreamingLDA
    _, sc = tiny_sharded
    kw = dict(num_topics=8, num_workers=2, seed=5, sampler_mode="mh",
              blocks_per_worker=2)
    a = StreamingLDA(sc, str(tmp_path / "straight"), **kw)
    for _ in range(4):
        a.step()
    b = StreamingLDA(sc, str(tmp_path / "killed"), **kw)
    b.step()
    b.step()
    b.save_checkpoint()
    b.step()                          # state now PAST the checkpoint
    c = StreamingLDA.resume(str(tmp_path / "killed"))
    assert c.iteration_count == 2     # rolled back to the checkpoint
    c.step()
    c.step()
    _assert_chains_equal(a, c, "streaming resume")
    assert c.iteration_count == 4


def test_streaming_resume_rejects_non_run_dir(tmp_path):
    from repro.core.engine.streaming import StreamingLDA
    with pytest.raises((ValueError, OSError)):
        StreamingLDA.resume(str(tmp_path / "nothing-here"))


# ---------------------------------------------------------------------------
# (3) device-engine checkpoint/resume — vmap, shard_map, and across
# ---------------------------------------------------------------------------

def _interrupted(corpus, path, make, stop=2, total=4):
    """Run ``stop`` iters, checkpoint, dirty the live state, resume, and
    finish to ``total`` iters.  Returns the resumed trainer."""
    b = make()
    for _ in range(stop):
        b.step()
    b.save_checkpoint(path)
    b.step()                          # past the checkpoint; discarded
    c = ModelParallelLDA.resume(corpus, path)
    assert c.iteration_count == stop
    for _ in range(total - stop):
        c.step()
    return c


@pytest.mark.parametrize("mode,s,d", [
    ("mh", 1, 1), ("sparse", 2, 1), ("scan", 1, 2),
])
def test_mp_resume_equals_uninterrupted(tiny_corpus, tmp_path, mode, s, d):
    corpus, _, _ = tiny_corpus
    kw = dict(num_topics=8, num_workers=2, seed=3, sampler_mode=mode,
              blocks_per_worker=s, data_parallel=d)
    a = ModelParallelLDA(corpus, **kw)
    for _ in range(4):
        a.step()
    c = _interrupted(corpus, str(tmp_path / "ck.npz"),
                     lambda: ModelParallelLDA(corpus, **kw))
    _assert_chains_equal(a, c, f"mp resume {mode} S={s} D={d}")
    assert c.iteration_count == 4


@pytest.mark.parametrize("s", [1, 2])
def test_mp_resume_across_backends(tiny_corpus, mesh2d, tmp_path, s):
    """Checkpoints are backend-agnostic: a shard_map checkpoint resumes
    bit-exactly on vmap and a vmap checkpoint on shard_map — both equal
    the uninterrupted vmap run."""
    corpus, _, _ = tiny_corpus
    kw = dict(num_topics=8, num_workers=2, seed=1, sampler_mode="mh",
              blocks_per_worker=s, data_parallel=2)
    a = ModelParallelLDA(corpus, **kw)
    for _ in range(4):
        a.step()

    # shard_map run -> checkpoint -> vmap resume
    b = ModelParallelLDA(corpus, **kw, backend="shard_map", mesh=mesh2d,
                         axis="model")
    b.step()
    b.step()
    p = str(tmp_path / "sm.npz")
    b.save_checkpoint(p)
    c = ModelParallelLDA.resume(corpus, p)            # vmap continuation
    c.step()
    c.step()
    _assert_chains_equal(a, c, f"shard_map ckpt -> vmap resume S={s}")

    # vmap run -> checkpoint -> shard_map resume
    v = ModelParallelLDA(corpus, **kw)
    v.step()
    v.step()
    q = str(tmp_path / "vm.npz")
    v.save_checkpoint(q)
    w = ModelParallelLDA.resume(corpus, q, backend="shard_map",
                                mesh=mesh2d, axis="model")
    w.step()
    w.step()
    _assert_chains_equal(a, w, f"vmap ckpt -> shard_map resume S={s}")


def test_mp_resume_rejects_wrong_corpus(tiny_corpus, small_corpus,
                                        tmp_path):
    """The corpus fingerprint guards against resuming onto different
    data — the layout is derived from the corpus, so a silent mismatch
    would scramble every assignment."""
    corpus, _, _ = tiny_corpus
    other, _, _ = small_corpus
    lda = ModelParallelLDA(corpus, num_topics=8, num_workers=2, seed=0)
    lda.step()
    p = str(tmp_path / "ck.npz")
    lda.save_checkpoint(p)
    with pytest.raises(ValueError, match="corpus does not match"):
        ModelParallelLDA.resume(other, p)


def test_host_oracle_resume_and_replay(tiny_corpus, tmp_path):
    """The KV-store oracle checkpoints/resumes bit-exactly too, and the
    resumed device engine still replays the resumed oracle draw for
    draw — the staleness contract survives a kill on either side."""
    corpus, _, _ = tiny_corpus
    hkw = dict(num_topics=8, num_workers=2, seed=7, blocks_per_worker=2,
               sampler="scan", ck_sync="round")
    ekw = dict(num_topics=8, num_workers=2, seed=7, blocks_per_worker=2,
               sampler_mode="scan")
    host_a = HostModelParallelLDA(corpus, **hkw)
    for _ in range(4):
        host_a.step()

    host_b = HostModelParallelLDA(corpus, **hkw)
    host_b.step()
    host_b.step()
    hp = str(tmp_path / "host.npz")
    host_b.save_checkpoint(hp)
    host_b.step()
    host_c = HostModelParallelLDA.resume(corpus, hp)
    host_c.step()
    host_c.step()
    np.testing.assert_array_equal(host_a.gather_ckt(),
                                  host_c.gather_ckt())
    np.testing.assert_array_equal(host_a.assignments(),
                                  host_c.assignments())

    eng = ModelParallelLDA(corpus, **ekw)
    eng.step()
    eng.step()
    ep = str(tmp_path / "eng.npz")
    eng.save_checkpoint(ep)
    eng_r = ModelParallelLDA.resume(corpus, ep)
    eng_r.step()
    eng_r.step()
    np.testing.assert_array_equal(np.asarray(eng_r.gather_counts().ckt),
                                  host_c.gather_ckt(),
                                  err_msg="resumed engine != resumed "
                                          "oracle")
    np.testing.assert_array_equal(eng_r.assignments(),
                                  host_c.assignments())


# ---------------------------------------------------------------------------
# satellite: the --sampler auto regime map (PR-6 measurements)
# ---------------------------------------------------------------------------

def test_regime_map_exact_at_measured_cells():
    for (k, length), family in REGIME_MAP.items():
        assert regime_sampler(k, length) == family, (k, length)


@pytest.mark.parametrize("k,length,family", [
    (16, 46, "sparse"),        # tiny K snaps to the (256, 48) cell
    (300, 200, "mh"),          # the short-K/long-doc MH corner
    (4096, 64, "sparse"),      # log2(64) is nearer 48 than 256
    (65536, 256, "sparse"),    # the big-model regime extrapolates
    (65536, 16, "sparse"),     # ... from the K=16384 row
    (2048, 16, "scan"),        # nearer the 4096 row than the 256 row
])
def test_regime_map_snaps_in_log_space(k, length, family):
    assert regime_sampler(k, length) == family


@pytest.mark.skipif(_on_tpu(), reason="auto resolves to Pallas on TPU")
def test_auto_uses_workload_and_falls_back():
    assert resolve_sampler_choice(
        "auto", num_topics=4096, max_doc_len=16) == "scan"
    assert resolve_sampler_choice(
        "auto", num_topics=16384, max_doc_len=100) == "sparse"
    # no workload parameters -> the pre-regime-map default
    assert resolve_sampler_choice("auto") == "mh"
    # explicit pallas off-TPU: refused without --force
    with pytest.raises(SystemExit):
        resolve_sampler_choice("mh_pallas")
    assert resolve_sampler_choice("mh_pallas", force=True) == "mh_pallas"


# ---------------------------------------------------------------------------
# sharded snapshot serving: row-restricted fold-in is bitwise the full one
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained_sharded_snapshot(tiny_sharded, tmp_path_factory):
    from repro.core.engine.streaming import StreamingLDA
    _, sc = tiny_sharded
    wd = tmp_path_factory.mktemp("snap")
    lda = StreamingLDA(sc, str(wd / "run"), num_topics=8, num_workers=2,
                       seed=2, sampler_mode="scan", blocks_per_worker=2)
    lda.step()
    lda.step()
    out = str(wd / "export")
    lda.save_snapshot_sharded(out)
    return lda.snapshot(), out


def test_sharded_snapshot_meta_and_blocks(trained_sharded_snapshot):
    full, snap_dir = trained_sharded_snapshot
    meta = load_sharded_snapshot_meta(snap_dir)
    assert meta["vocab_size"] == full.vocab_size
    assert meta["num_topics"] == full.num_topics
    blocks = [np.load(os.path.join(snap_dir, f"block_{b:05d}.npy"))
              for b in range(meta["num_blocks"])]
    np.testing.assert_array_equal(
        np.concatenate(blocks)[:full.vocab_size], np.asarray(full.ckt))


def test_sharded_snapshot_rejects_bad_dir(tmp_path):
    with pytest.raises(ValueError, match="not a sharded snapshot"):
        load_sharded_snapshot_meta(str(tmp_path))
    (tmp_path / "meta.json").write_text('{"format": "something-else"}')
    with pytest.raises(ValueError, match="unknown snapshot format"):
        load_sharded_snapshot_meta(str(tmp_path))


def test_row_restricted_fold_in_bitwise(trained_sharded_snapshot):
    """Serving from the row-restricted view — only the rows the batch's
    distinct words touch, with ``true_vocab_size`` keeping the ``Vβ``
    smoothing honest — folds in BITWISE equal to the full snapshot."""
    full, snap_dir = trained_sharded_snapshot
    rng = np.random.default_rng(9)
    docs = [rng.integers(0, full.vocab_size, size=n).astype(np.int32)
            for n in (12, 5, 20)]
    word, mask = pack_queries(docs, t_pad=24, q_pad=4)

    sub, remapped = load_snapshot_rows(snap_dir, word)
    assert sub.true_vocab_size == full.vocab_size
    assert sub.vocab_size == np.unique(word).shape[0]
    assert sub.vbeta == full.vbeta
    # the view holds exactly the referenced rows
    np.testing.assert_array_equal(np.asarray(sub.ckt)[remapped],
                                  np.asarray(full.ckt)[word])

    for sampler in ("scan", "mh", "sparse"):
        a = fold_in(full, word, mask, num_sweeps=3, sampler=sampler,
                    seed=4)
        b = fold_in(sub, remapped, mask, num_sweeps=3, sampler=sampler,
                    seed=4)
        np.testing.assert_array_equal(
            np.asarray(a.theta), np.asarray(b.theta),
            err_msg=f"{sampler}: row-restricted theta diverged")
