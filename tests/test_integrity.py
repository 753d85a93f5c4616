"""Integrity layer (DESIGN.md §15): sidecar checksums, atomic writes,
torn-write detection, and validate-on-load across every artifact family
— corpus shards, engine checkpoints, serving snapshots.  The acceptance
criterion pinned here: a bit-flipped checkpoint, corpus shard, and
snapshot are each REJECTED with a structured error, never loaded
silently."""
import json
import os

import numpy as np
import pytest

from repro.data import integrity
from repro.data.integrity import (CorruptArtifactError, IntegrityError,
                                  MissingArtifactError, TornWriteError)


# ---------------------------------------------------------------------------
# Sidecars and validation primitives
# ---------------------------------------------------------------------------

class TestSidecars:
    def test_save_npy_roundtrip_with_sidecar(self, tmp_path):
        p = str(tmp_path / "a.npy")
        arr = np.arange(12, dtype=np.int32).reshape(3, 4)
        integrity.save_npy(p, arr)
        assert os.path.exists(integrity.sidecar_path(p))
        assert integrity.validate_file(p) is True
        out = integrity.load_npy(p)
        assert out.dtype == arr.dtype and np.array_equal(out, arr)

    def test_save_npz_roundtrip(self, tmp_path):
        p = str(tmp_path / "a.npz")
        integrity.save_npz(p, x=np.arange(5), y=np.ones((2, 2)))
        d = integrity.load_npz(p)
        assert set(d) == {"x", "y"}
        assert np.array_equal(d["x"], np.arange(5))

    def test_unstamped_file_passes_without_requirement(self, tmp_path):
        p = str(tmp_path / "plain.npy")
        np.save(p, np.zeros(3))
        assert integrity.validate_file(p) is False      # no sidecar, ok
        with pytest.raises(MissingArtifactError):
            integrity.validate_file(p, require_sidecar=True)

    def test_missing_artifact(self, tmp_path):
        with pytest.raises(MissingArtifactError):
            integrity.validate_file(str(tmp_path / "nope.npy"))
        with pytest.raises(MissingArtifactError):
            integrity.load_npy(str(tmp_path / "nope.npy"))

    def test_bit_flip_detected(self, tmp_path):
        p = str(tmp_path / "a.npy")
        integrity.save_npy(p, np.arange(100, dtype=np.float64))
        integrity.flip_byte(p, seed=3)
        with pytest.raises(CorruptArtifactError):
            integrity.load_npy(p)

    def test_flip_byte_is_deterministic(self, tmp_path):
        p1, p2 = str(tmp_path / "a.bin"), str(tmp_path / "b.bin")
        for p in (p1, p2):
            with open(p, "wb") as f:
                f.write(bytes(range(200)))
        assert integrity.flip_byte(p1, seed=7) == \
            integrity.flip_byte(p2, seed=7)
        with open(p1, "rb") as f1, open(p2, "rb") as f2:
            assert f1.read() == f2.read()

    def test_torn_write_detected_as_torn(self, tmp_path):
        p = str(tmp_path / "a.npy")
        integrity.save_npy(p, np.arange(1000, dtype=np.int64))
        integrity.truncate_file(p, os.path.getsize(p) // 2)
        with pytest.raises(TornWriteError):
            integrity.validate_file(p)
        # TornWriteError IS a CorruptArtifactError (one catch for "bad")
        with pytest.raises(CorruptArtifactError):
            integrity.validate_file(p)

    def test_sha256_option(self, tmp_path):
        p = str(tmp_path / "a.npy")
        np.save(p, np.arange(4))
        integrity.write_sidecar(p, algo="sha256")
        assert integrity.validate_file(p) is True
        integrity.flip_byte(p, seed=0)
        with pytest.raises(CorruptArtifactError):
            integrity.validate_file(p)

    def test_validate_tree(self, tmp_path):
        root = tmp_path / "tree"
        (root / "sub").mkdir(parents=True)
        integrity.save_npy(str(root / "a.npy"), np.zeros(3))
        integrity.save_npy(str(root / "sub" / "b.npy"), np.ones(3))
        assert integrity.validate_tree(str(root)) == 2
        integrity.flip_byte(str(root / "sub" / "b.npy"), seed=1)
        with pytest.raises(CorruptArtifactError):
            integrity.validate_tree(str(root))

    def test_unreadable_sidecar_is_corrupt(self, tmp_path):
        p = str(tmp_path / "a.npy")
        integrity.save_npy(p, np.zeros(2))
        with open(integrity.sidecar_path(p), "w") as f:
            f.write("{not json")
        with pytest.raises(CorruptArtifactError):
            integrity.validate_file(p)


class TestAtomicJson:
    def test_roundtrip_and_checksum(self, tmp_path):
        p = str(tmp_path / "cfg.json")
        integrity.atomic_write_json(p, {"a": 1}, checksum=True)
        assert integrity.validate_file(p) is True
        with open(p) as f:
            assert json.load(f) == {"a": 1}

    def test_overwrite_leaves_no_temp(self, tmp_path):
        p = str(tmp_path / "cfg.json")
        integrity.atomic_write_json(p, {"v": 1})
        integrity.atomic_write_json(p, {"v": 2})
        assert json.load(open(p)) == {"v": 2}
        assert not os.path.exists(p + ".tmp")


# ---------------------------------------------------------------------------
# The three artifact families of the acceptance criterion
# ---------------------------------------------------------------------------

class TestArtifactFamilies:
    def test_bit_flipped_corpus_shard_rejected(self, tmp_path):
        from repro.data.stream import ShardedCorpus, write_zipf_stream
        out = write_zipf_stream(str(tmp_path / "c"), 12, 64, 8, seed=0,
                                docs_per_shard=4)
        sc = ShardedCorpus(out)
        shard_file = os.path.join(out, sc.meta["shards"][1]["file"])
        integrity.flip_byte(shard_file, seed=2)
        sc.load_shard(0)                        # untouched shard still fine
        with pytest.raises(CorruptArtifactError):
            sc.load_shard(1)

    def test_bit_flipped_mp_checkpoint_rejected(self, tmp_path):
        from repro.core.engine import ModelParallelLDA
        from repro.data.synthetic import synthetic_corpus
        corpus, _, _ = synthetic_corpus(12, 32, 4, 8, seed=0)
        lda = ModelParallelLDA(corpus, 4, 2, seed=0)
        lda.step()
        ckpt = str(tmp_path / "ck.npz")
        lda.save_checkpoint(ckpt)
        assert os.path.exists(integrity.sidecar_path(ckpt))
        integrity.flip_byte(ckpt, seed=5)
        with pytest.raises(CorruptArtifactError):
            ModelParallelLDA.resume(corpus, ckpt)

    def test_bit_flipped_snapshot_npz_rejected(self, tmp_path):
        from repro.core.infer import ModelSnapshot, load_snapshot
        snap = ModelSnapshot.from_counts(
            np.arange(32 * 4, dtype=np.int32).reshape(32, 4),
            np.arange(4, dtype=np.int32) * 32, 0.1, 0.01)
        p = str(tmp_path / "snap.npz")
        snap.save(p)
        assert load_snapshot(p).fingerprint() == snap.fingerprint()
        integrity.flip_byte(p, seed=9)
        with pytest.raises(CorruptArtifactError):
            load_snapshot(p)

    def test_bit_flipped_sharded_snapshot_block_rejected(self, tmp_path):
        from repro.core.engine.streaming import StreamingLDA
        from repro.core.infer import load_snapshot_rows
        from repro.data.stream import write_zipf_stream
        cdir = write_zipf_stream(str(tmp_path / "c"), 12, 48, 8, seed=1,
                                 docs_per_shard=6)
        lda = StreamingLDA(cdir, str(tmp_path / "wd"), 4, 2, seed=0)
        lda.step()
        sd = lda.save_snapshot_sharded(str(tmp_path / "snap"))
        words = np.arange(8, dtype=np.int32)
        load_snapshot_rows(sd, words)           # validates clean
        integrity.flip_byte(os.path.join(sd, "block_00000.npy"), seed=4)
        with pytest.raises(CorruptArtifactError):
            load_snapshot_rows(sd, words)

    def test_streaming_resume_rejects_flipped_checkpoint(self, tmp_path):
        from repro.core.engine.streaming import StreamingLDA
        from repro.data.stream import write_zipf_stream
        cdir = write_zipf_stream(str(tmp_path / "c"), 12, 48, 8, seed=1,
                                 docs_per_shard=6)
        wd = str(tmp_path / "wd")
        lda = StreamingLDA(cdir, wd, 4, 2, seed=0)
        lda.step()
        lda.save_checkpoint()
        integrity.flip_byte(os.path.join(wd, "ckpt", "ck.npy"), seed=6)
        with pytest.raises(CorruptArtifactError):
            StreamingLDA.resume(wd)

    def test_error_taxonomy_hierarchy(self):
        assert issubclass(TornWriteError, CorruptArtifactError)
        assert issubclass(CorruptArtifactError, IntegrityError)
        assert issubclass(MissingArtifactError, IntegrityError)
