"""Pluggable CountStore layer (DESIGN.md §16).

The claim under test is BITWISE STORE-INVARIANCE: the choice of model
storage — dense ``[Vb, K]`` blocks vs. the hybrid dense-head/sparse-tail
record — never changes a chain, only its memory layout.  Pillars:

1. **The store is an exact integer codec.**  ``from_dense``/``to_dense``,
   row reads, column sums, and the COO delta fold all round-trip int32
   counts exactly, including head-row promote/demote across the ``wcap``
   threshold; underflow (a corrupt delta stream) raises instead of
   wrapping.
2. **The tail-native sampler equals the dense sampler.**
   ``sweep_block_sparse_tail`` consumes the TailStore's lane layout with
   zero conversion and must equal ``sweep_block_sparse`` draw-for-draw
   (the batch-dim-invariant cumsum + masked-garbage-gather argument of
   §16) at geometries with many, one, and zero overflow rows.
3. **Store-invariance composes through every layer.**  Streaming
   tail == streaming dense (both the sparse store-native path and the
   scan densify path) == in-memory engine; checkpoints cross-resume in
   BOTH directions across formats (v1 dense record ↔ v2 store record);
   the host KV-store oracle under a tail-encoded store replays the
   engine; sharded snapshots round-trip through the row-restricted
   serving load.
4. **Persistence is §15-integrity-covered.**  Store records publish
   atomically with crc sidecars; a flipped bit or torn write surfaces
   through the taxonomy, never as silently-decoded garbage.

Plus the CLI satellites: the ``--store auto`` decision table (regime-map
derived) and the occupancy-aware ``memory_report``/``store_note``.
"""
import json
import os

import numpy as np
import pytest

from repro.core.engine import countstore
from repro.core.engine.countstore import (DEFAULT_TAIL_WCAP, DenseStore,
                                          TailStore, available_stores,
                                          resolve_store)
from repro.core.engine import ModelParallelLDA
from repro.data.integrity import (CorruptArtifactError, TornWriteError,
                                  flip_byte, truncate_file)
from repro.data.stream import ShardedCorpus, shard_corpus

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _assert_chains_equal(a, b, ctx: str):
    sa, sb = a.gather_counts(), b.gather_counts()
    np.testing.assert_array_equal(np.asarray(sa.ckt), np.asarray(sb.ckt),
                                  err_msg=f"{ctx}: ckt diverged")
    np.testing.assert_array_equal(np.asarray(sa.cdk), np.asarray(sb.cdk),
                                  err_msg=f"{ctx}: cdk diverged")
    np.testing.assert_array_equal(np.asarray(sa.ck), np.asarray(sb.ck),
                                  err_msg=f"{ctx}: ck diverged")
    np.testing.assert_array_equal(a.assignments(), b.assignments(),
                                  err_msg=f"{ctx}: z diverged")


def _zipf_dense(vb, k, wcap, seed=0, heads=3):
    """A [vb, k] count block with a few heavy rows (nnz > wcap) and a
    long tail of light rows — the §16 working regime."""
    rng = np.random.default_rng(seed)
    dense = np.zeros((vb, k), np.int32)
    for r in range(vb):
        nnz = min(k, 2 * wcap if r < heads else rng.integers(0, wcap + 1))
        cols = rng.choice(k, size=nnz, replace=False)
        dense[r, cols] = rng.integers(1, 9, size=nnz)
    return dense


# ---------------------------------------------------------------------------
# (1) the store as an exact integer codec
# ---------------------------------------------------------------------------

def test_registry_and_defaults():
    assert available_stores() == ["dense", "tail"]
    assert resolve_store("dense") is DenseStore
    assert resolve_store("tail") is TailStore
    with pytest.raises(ValueError, match="unknown store"):
        resolve_store("bogus")
    # the store's head/tail threshold and the sparse sampler's must be
    # the same number, or the lane layouts disagree silently
    from repro.core.sparse_device import DEFAULT_WCAP
    assert DEFAULT_TAIL_WCAP == DEFAULT_WCAP


@pytest.mark.parametrize("kind", ["dense", "tail"])
def test_roundtrip_rows_colsums(kind):
    dense = _zipf_dense(24, 32, wcap=6, seed=1)
    st = resolve_store(kind).from_dense(dense, wcap=6)
    assert st.shape == (24, 32)
    np.testing.assert_array_equal(st.to_dense(), dense)
    idx = np.array([0, 3, 3, 23, 7])
    np.testing.assert_array_equal(st.rows(idx), dense[idx])
    np.testing.assert_array_equal(st.col_sums(),
                                  dense.sum(axis=0, dtype=np.int64))
    occ = st.occupancy()
    assert occ["kind"] == kind and occ["rows"] == 24
    assert st.nbytes_resident() == occ["nbytes_resident"] > 0
    if kind == "tail":
        assert occ["overflow_rows"] == 3         # the planted heavy rows
        assert occ["head_rows"] + occ["tail_rows"] == 24


def test_tail_apply_coo_promote_demote_underflow():
    wcap = 4
    dense = _zipf_dense(12, 16, wcap=wcap, seed=2)
    st = TailStore.from_dense(dense, wcap=wcap)
    # promote: pile counts onto a light row until nnz > wcap
    light = int(np.argmin((dense > 0).sum(axis=1)))
    rows = np.full(wcap + 2, light)
    topics = np.arange(wcap + 2)
    st.apply_coo(rows, topics, np.ones(wcap + 2, np.int64))
    dense[light, :wcap + 2] += 1
    np.testing.assert_array_equal(st.to_dense(), dense)
    assert light in set(np.asarray(st.over_rows).tolist())
    # demote: drain a heavy row back under the threshold
    heavy = 0
    cols = np.nonzero(dense[heavy])[0]
    drain = cols[wcap - 1:]
    st.apply_coo(np.full(drain.size, heavy), drain,
                 -dense[heavy, drain].astype(np.int64))
    dense[heavy, drain] = 0
    np.testing.assert_array_equal(st.to_dense(), dense)
    assert heavy not in set(np.asarray(st.over_rows).tolist())
    # a delta stream that would go negative is corrupt — raise, don't wrap
    with pytest.raises(ValueError, match="underflow"):
        st.apply_coo(np.array([light]), np.array([0]),
                     np.array([-10 ** 6]))


@pytest.mark.parametrize("kind", ["dense", "tail"])
def test_apply_token_delta_matches_dense_fold(kind):
    rng = np.random.default_rng(3)
    dense = _zipf_dense(10, 12, wcap=3, seed=3) + 5   # every topic legal
    st = resolve_store(kind).from_dense(dense, wcap=3)
    n = 40
    rows = rng.integers(0, 10, n).astype(np.int32)
    z_old = rng.integers(0, 12, n).astype(np.int32)
    z_new = rng.integers(0, 12, n).astype(np.int32)
    st.apply_token_delta(rows, z_old, z_new)
    np.add.at(dense, (rows, z_old), -1)
    np.add.at(dense, (rows, z_new), 1)
    np.testing.assert_array_equal(st.to_dense(), dense)


# ---------------------------------------------------------------------------
# (4) persistence: record format + §15 integrity taxonomy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "tail"])
def test_save_load_dispatch_and_artifact_swap(kind, tmp_path):
    dense = _zipf_dense(8, 16, wcap=4, seed=4)
    stem = str(tmp_path / "block_00000")
    st = resolve_store(kind).from_dense(dense, wcap=4)
    st.save(stem)
    ext = ".npy" if kind == "dense" else ".npz"
    assert os.path.exists(stem + ext)
    assert countstore.exists(stem)
    back = countstore.load(stem)
    assert type(back) is type(st)
    np.testing.assert_array_equal(back.to_dense(), dense)
    # re-saving under the OTHER kind must retire the old artifact, so a
    # stem never holds two decodable generations at once
    other = "tail" if kind == "dense" else "dense"
    resolve_store(other).from_dense(dense, wcap=4).save(stem)
    assert not os.path.exists(stem + ext)
    assert type(countstore.load(stem)) is resolve_store(other)
    np.testing.assert_array_equal(countstore.load(stem).to_dense(), dense)


def test_dense_store_file_is_plain_npy(tmp_path):
    """Backward compat: a DenseStore block file is byte-identical to the
    pre-§16 raw ``integrity.save_npy`` block file, so old workdirs load
    and dense-store runs write the frozen format."""
    from repro.data import integrity
    dense = _zipf_dense(8, 16, wcap=4, seed=5)
    a = str(tmp_path / "a")
    b = str(tmp_path / "b")
    DenseStore.from_dense(dense, wcap=4).save(a)
    integrity.save_npy(b + ".npy", dense)
    with open(a + ".npy", "rb") as fa, open(b + ".npy", "rb") as fb:
        assert fa.read() == fb.read()
    np.testing.assert_array_equal(countstore.load(b).to_dense(), dense)


def test_missing_and_corrupt_records(tmp_path):
    from repro.data.integrity import MissingArtifactError
    stem = str(tmp_path / "block_00000")
    with pytest.raises(MissingArtifactError):
        countstore.load(stem)
    dense = _zipf_dense(8, 16, wcap=4, seed=6)
    TailStore.from_dense(dense, wcap=4).save(stem)
    # bit-flip -> checksum mismatch
    flip_byte(stem + ".npz", seed=1)
    with pytest.raises(CorruptArtifactError):
        countstore.load(stem)
    # torn write -> truncation class
    TailStore.from_dense(dense, wcap=4).save(stem)
    truncate_file(stem + ".npz", os.path.getsize(stem + ".npz") // 2)
    with pytest.raises(TornWriteError):
        countstore.load(stem)


def test_pack_unpack_record():
    dense = _zipf_dense(8, 16, wcap=4, seed=7)
    st = TailStore.from_dense(dense, wcap=4)
    aux, arrays = st.pack()
    assert aux["kind"] == "tail"
    # aux must be JSON-clean (it rides checkpoint config channels)
    aux2 = json.loads(json.dumps(aux))
    back = countstore.unpack_record(
        aux2, {k: np.asarray(v) for k, v in arrays.items()})
    np.testing.assert_array_equal(back.to_dense(), dense)


# ---------------------------------------------------------------------------
# (2) tail-native sampler == dense sampler, draw-for-draw
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("vb,k,wcap,heads", [
    (32, 64, 8, 16),    # many overflow rows
    (16, 128, 32, 1),   # exactly one
    (8, 16, 8, 0),      # none — pure tail
])
def test_tail_sweep_bitwise_equals_dense_sweep(vb, k, wcap, heads):
    import jax.numpy as jnp

    from repro.core.sparse_device import (sweep_block_sparse,
                                          sweep_block_sparse_tail)
    rng = np.random.default_rng(8)
    ckt = _zipf_dense(vb, k, wcap=wcap, seed=8, heads=heads)
    if heads == 0:      # clamp every row under the threshold
        keep = np.argsort(ckt, axis=1)[:, -wcap:]
        m = np.zeros_like(ckt, bool)
        np.put_along_axis(m, keep, True, axis=1)
        ckt = np.where(m, ckt, 0).astype(np.int32)
    n, dloc, dcap = 96, 6, 32
    doc = rng.integers(0, dloc, n).astype(np.int32)
    woff = rng.integers(0, vb, n).astype(np.int32)
    mask = rng.random(n) < 0.9
    # z consistent with the frozen block: every token's topic has count
    z = np.zeros(n, np.int32)
    for i in range(n):
        cols = np.nonzero(ckt[woff[i]])[0]
        z[i] = cols[rng.integers(0, cols.size)] if cols.size \
            else rng.integers(0, k)
        ckt[woff[i], z[i]] += 1
    cdk = np.zeros((dloc, k), np.int32)
    np.add.at(cdk, (doc[mask], z[mask]), 1)
    ck = ckt.sum(axis=0).astype(np.int32)
    u = rng.random(n).astype(np.float32)
    alpha = np.full(k, 0.1, np.float32)
    beta, vbeta = np.float32(0.01), np.float32(0.01 * vb)

    d_out = sweep_block_sparse(
        jnp.asarray(cdk), jnp.asarray(ckt), jnp.asarray(ck),
        jnp.asarray(doc), jnp.asarray(woff), jnp.asarray(z),
        jnp.asarray(mask), jnp.asarray(u), jnp.asarray(alpha),
        beta, vbeta, dcap=dcap, wcap=wcap)

    st = TailStore.from_dense(ckt, wcap=wcap)
    dev = st.device_operands()
    t_out = sweep_block_sparse_tail(
        jnp.asarray(cdk), jnp.asarray(dev["tail_topics"]),
        jnp.asarray(dev["tail_counts"]), jnp.asarray(dev["over_pad"]),
        jnp.asarray(dev["row_map"]), jnp.asarray(ck),
        jnp.asarray(doc), jnp.asarray(woff), jnp.asarray(z),
        jnp.asarray(mask), jnp.asarray(u), jnp.asarray(alpha),
        beta, vbeta, dcap=dcap)

    np.testing.assert_array_equal(np.asarray(t_out[2]),
                                  np.asarray(d_out[3]),
                                  err_msg="z diverged")
    np.testing.assert_array_equal(np.asarray(t_out[0]),
                                  np.asarray(d_out[0]),
                                  err_msg="cdk diverged")
    np.testing.assert_array_equal(np.asarray(t_out[1]),
                                  np.asarray(d_out[2]),
                                  err_msg="ck diverged")
    # the store-side token fold reproduces the dense sampler's block
    z_new = np.asarray(t_out[2])
    st.apply_token_delta(woff[mask], z[mask], z_new[mask])
    np.testing.assert_array_equal(st.to_dense(), np.asarray(d_out[1]),
                                  err_msg="store fold != dense block")


# ---------------------------------------------------------------------------
# (3) store-invariance through the engines
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def zipf_sharded(tmp_path_factory):
    from repro.data.synthetic import synthetic_corpus
    corpus, _, _ = synthetic_corpus(num_docs=32, vocab_size=96,
                                    num_topics=4, doc_len=24, seed=9)
    out = str(tmp_path_factory.mktemp("cs_sharded") / "corpus")
    shard_corpus(corpus, out, num_shards=2)
    return corpus, ShardedCorpus(out)


@pytest.mark.parametrize("mode", ["sparse", "scan"])
def test_streaming_tail_equals_dense(zipf_sharded, tmp_path, mode):
    """sparse = the store-native lane path; scan = the explicit
    ``to_dense`` escape hatch.  Both must be chain-invariant."""
    from repro.core.engine.streaming import StreamingLDA
    _, sc = zipf_sharded
    kw = dict(num_topics=16, num_workers=2, seed=13, sampler_mode=mode,
              blocks_per_worker=2)
    if mode == "sparse":
        kw["sampler_args"] = (("dcap", 32), ("wcap", 8))
    a = StreamingLDA(sc, str(tmp_path / "dense"), store="dense", **kw)
    b = StreamingLDA(sc, str(tmp_path / "tail"), store="tail", **kw)
    for _ in range(2):
        a.step()
        b.step()
    _assert_chains_equal(a, b, f"streaming tail vs dense ({mode})")
    assert a._rng.bit_generator.state == b._rng.bit_generator.state
    # densification is never silent: native path has no note, the
    # escape hatch names its per-round [Vb, K] cost
    if mode == "sparse":
        assert b.store_note() is None
        assert any(f.endswith(".npz")
                   for f in os.listdir(tmp_path / "tail" / "state"
                                       / "blocks"))
    else:
        assert "densifies" in b.store_note()
    assert a.store_note() is None
    rep = b.memory_report()
    assert rep["store"] == "tail"
    occ = rep["store_occupancy"]
    assert occ["head_rows"] + occ["tail_rows"] \
        == b.num_blocks * b.partition.block_size
    assert rep["resident_store_bytes"] > 0
    # legacy dense-model accounting is untouched
    assert rep["resident_block_bytes"] * b.num_blocks \
        >= rep["total_model_bytes"]


def test_streaming_tail_equals_in_memory(zipf_sharded, tmp_path):
    from repro.core.engine.streaming import StreamingLDA
    corpus, sc = zipf_sharded
    args = (("dcap", 32), ("wcap", 8))
    mem = ModelParallelLDA(corpus, num_topics=16, num_workers=2, seed=17,
                           sampler_mode="sparse", sampler_args=args,
                           store="tail")
    disk = StreamingLDA(sc, str(tmp_path / "run"), num_topics=16,
                        num_workers=2, seed=17, sampler_mode="sparse",
                        sampler_args=args, store="tail")
    for _ in range(2):
        mem.step()
        disk.step()
    _assert_chains_equal(mem, disk, "streaming tail vs in-memory tail")


def test_streaming_cross_store_resume(zipf_sharded, tmp_path):
    """A checkpoint written under one store resumes bitwise under the
    other — count encode/decode is an exact integer round-trip, so the
    chain cannot tell its blocks were re-encoded."""
    from repro.core.engine.streaming import StreamingLDA
    _, sc = zipf_sharded
    kw = dict(num_topics=16, num_workers=2, seed=19,
              sampler_mode="sparse", blocks_per_worker=2,
              sampler_args=(("dcap", 32), ("wcap", 8)))
    ref = StreamingLDA(sc, str(tmp_path / "ref"), store="dense", **kw)
    for _ in range(4):
        ref.step()
    for src, dst in (("tail", "dense"), ("dense", "tail")):
        wd = str(tmp_path / f"{src}2{dst}")
        a = StreamingLDA(sc, wd, store=src, **kw)
        a.step()
        a.step()
        a.save_checkpoint()
        b = StreamingLDA.resume(wd, store=dst)
        assert b.store_kind == dst
        cfg = json.load(open(os.path.join(wd, "run.json")))
        assert cfg["store"] == dst      # the switch is durable
        b.step()
        b.step()
        _assert_chains_equal(ref, b, f"resume {src}->{dst}")
        assert ref._rng.bit_generator.state == b._rng.bit_generator.state


def test_mp_engine_cross_store_checkpoint(zipf_sharded, tmp_path):
    """In-memory engine: dense writes the bitwise-frozen v1 record, tail
    the v2 per-slot store record; each resumes under the other store and
    continues the identical chain."""
    corpus, _ = zipf_sharded
    kw = dict(num_topics=16, num_workers=2, blocks_per_worker=2, seed=23,
              sampler_mode="sparse",
              sampler_args=(("dcap", 32), ("wcap", 8)))
    ref = ModelParallelLDA(corpus, store="dense", **kw)
    ref.run(4)
    for src, dst in (("tail", "dense"), ("dense", "tail")):
        a = ModelParallelLDA(corpus, store=src, **kw)
        a.run(2)
        p = a.save_checkpoint(str(tmp_path / f"ck_{src}"))
        data = np.load(p)
        cfg = json.loads(bytes(data["config"]).decode())
        if src == "dense":
            assert cfg["format"] == ModelParallelLDA.CKPT_FORMAT
            assert "ckt" in data.files       # v1 record frozen
        else:
            assert cfg["format"] == ModelParallelLDA.CKPT_FORMAT_V2
            assert "ckt" not in data.files
            assert "store_aux" in data.files
        b = ModelParallelLDA.resume(corpus, p, store=dst)
        assert b.store_kind == dst
        b.run(2)
        _assert_chains_equal(ref, b, f"mp resume {src}->{dst}")


def test_sharded_snapshot_v2_roundtrip(zipf_sharded, tmp_path):
    """Tail runs export ``sharded-snapshot-v2``; the row-restricted
    serving load decodes exactly the rows a batch touches and matches
    the dense run's v1 export bit-for-bit."""
    from repro.core.engine.streaming import StreamingLDA
    from repro.core.infer import (load_sharded_snapshot_meta,
                                  load_snapshot_rows)
    _, sc = zipf_sharded
    kw = dict(num_topics=16, num_workers=2, seed=29,
              sampler_mode="sparse",
              sampler_args=(("dcap", 32), ("wcap", 8)))
    snaps = {}
    for kind in ("dense", "tail"):
        lda = StreamingLDA(sc, str(tmp_path / f"run_{kind}"),
                           store=kind, **kw)
        lda.step()
        out = str(tmp_path / f"snap_{kind}")
        lda.save_snapshot_sharded(out)
        snaps[kind] = out
    m1 = load_sharded_snapshot_meta(snaps["dense"])
    m2 = load_sharded_snapshot_meta(snaps["tail"])
    assert m1["format"] == "sharded-snapshot-v1"     # frozen
    assert m2["format"] == "sharded-snapshot-v2"
    assert (m1["store"], m2["store"]) == ("dense", "tail")
    words = np.array([0, 5, 5, 91, 44, 17], np.int32)
    s1, r1 = load_snapshot_rows(snaps["dense"], words)
    s2, r2 = load_snapshot_rows(snaps["tail"], words)
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(s1.ckt, s2.ckt)
    np.testing.assert_array_equal(s1.ck, s2.ck)


def test_kvstore_oracle_tail_equals_dense(zipf_sharded):
    """The host oracle is the §16 numpy mirror: a tail-encoded KV store
    replays the dense one draw-for-draw, and both replay the engine."""
    from repro.core.kvstore import HostModelParallelLDA
    corpus, _ = zipf_sharded
    kw = dict(num_topics=16, num_workers=2, blocks_per_worker=2, seed=31,
              sampler_args=(("dcap", 32), ("wcap", 8)))
    hd = HostModelParallelLDA(corpus, sampler="sparse", ck_sync="round",
                              store="dense", **kw)
    ht = HostModelParallelLDA(corpus, sampler="sparse", ck_sync="round",
                              store="tail", **kw)
    eng = ModelParallelLDA(corpus, sampler_mode="sparse", store="tail",
                           **kw)
    for _ in range(2):
        hd.step()
        ht.step()
    eng.run(2)
    np.testing.assert_array_equal(hd.assignments(), ht.assignments())
    np.testing.assert_array_equal(hd.gather_ckt(), ht.gather_ckt())
    np.testing.assert_array_equal(ht.assignments(), eng.assignments())
    # logical dense traffic (the §3.2 cost model) is encoding-invariant
    assert hd.store.bytes_moved == ht.store.bytes_moved
    assert ht.store.resident_bytes() > 0


# ---------------------------------------------------------------------------
# CLI satellites: --store auto decision table, config-echo notes
# ---------------------------------------------------------------------------

def test_resolve_store_choice_decision_table():
    from repro.launch.samplers import (REGIME_MAP, resolve_store_choice,
                                       store_choices)
    assert store_choices() == ["dense", "tail", "auto"]
    assert resolve_store_choice("dense") == "dense"
    assert resolve_store_choice("tail") == "tail"
    # auto == tail exactly where the regime map picks the sparse family
    for (k, dl), fam in REGIME_MAP.items():
        got = resolve_store_choice("auto", num_topics=k, max_doc_len=dl)
        assert got == ("tail" if fam == "sparse" else "dense"), (k, dl)
    # unknown workload (no corpus yet) -> the conservative default
    assert resolve_store_choice("auto") == "dense"
    with pytest.raises(SystemExit, match="unknown store"):
        resolve_store_choice("bogus")


def test_mp_engine_store_note_and_report(zipf_sharded):
    corpus, _ = zipf_sharded
    d = ModelParallelLDA(corpus, num_topics=16, num_workers=2, seed=1)
    t = ModelParallelLDA(corpus, num_topics=16, num_workers=2, seed=1,
                         store="tail")
    assert d.store_note() is None
    assert "dense device chain" in t.store_note()
    rep = t.memory_report()
    assert rep["store"] == "tail"
    occ = rep["store_occupancy"]
    assert occ["head_rows"] + occ["tail_rows"] > 0
    assert rep["total_store_bytes"] > 0
    assert "store_occupancy" not in d.memory_report()
