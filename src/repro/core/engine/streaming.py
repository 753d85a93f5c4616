"""Out-of-core streaming trainer: the big-model regime (DESIGN.md §13).

:class:`StreamingLDA` runs the exact model-parallel chain of
:class:`~repro.core.engine.api.ModelParallelLDA` with BOTH halves of the
state out of core:

* the corpus stays in its sharded on-disk format (`data/stream.py`) and
  is demultiplexed once into per-(grid row, block) token files under a
  working directory — training never holds the full token stream;
* the model blocks live in a disk-backed block store (one
  :class:`~repro.core.engine.countstore.CountStore` record per
  ``[Vb, K]`` block — a plain ``.npy`` for the dense store, a
  ``store-v2`` ``.npz`` for the tail store; DESIGN.md §16 — the paper's
  key-value store made literal), and at most ONE block (plus its
  traveling table, for the MH family) is in memory at any time.

Peak training memory is therefore bounded by the resident ``[Vb, K]``
block and one in-flight row/block token group, independent of corpus
size and of total model size ``V × K`` — the paper's capacity claim
(unmeasured on the chip).

Bit-exactness.  The scheduler is the serial transcript of the SPMD
engine — the same frozen-per-round semantics as the host oracle
(`core/kvstore.py`): within a round every replica samples frozen
round-start block copies and frozen ``{C_k}``, deltas are reconciled and
committed at the round boundary.  The rng stream is the engine's own:
numpy ``Generator`` fills arrays sequentially from the bit stream, so
drawing ``z0`` chunk-by-chunk in disk-shard order and uniforms
round-by-row in grid order reproduces the engine's one-shot
``integers(0, K, N)`` / ``random((B, R, cap))`` draws bit-for-bit (the
property is pinned by ``tests/test_stream_resume.py``).  Per-row calls
into the SAME jitted registry samplers equal the engine's vmap over rows
— the structural-equivalence argument the oracle already proves — so a
streaming run is draw-for-draw identical to the in-memory engine at any
``(D, M, S)``, any sampler, both table lifetimes.

Checkpoint/resume.  The working directory *is* the persistent state:
``static/`` holds the immutable layout, ``state/`` the mutable chain
(blocks, ``C_k``, per-row ``z``/``cdk``, rng bit-generator state,
iteration count).  :meth:`save_checkpoint` snapshots ``state/`` into
``ckpt/`` with an atomic directory swap at an iteration boundary — where
the table queue is empty and every replica agrees, so nothing
sampler-specific needs saving — and :meth:`StreamingLDA.resume` restores
it; a resumed run re-draws from the restored bit-generator state and is
bit-identical to an uninterrupted one.
"""
from __future__ import annotations

import json
import os
import shutil
from typing import Dict, List, Optional

import numpy as np

from repro.core import faults, schedule as sched
from repro.core.engine import countstore
from repro.core.invindex import build_inverted_index
from repro.data import integrity
from repro.data.stream import ShardedCorpus

RUN_JSON = "run.json"
PROGRESS_JSON = "progress.json"


def _save_npy(path: str, arr: np.ndarray) -> None:
    # atomic publish + crc32 sidecar: a kill at any instant leaves the
    # previous complete array or the new one, and a later bit flip is
    # caught at load (DESIGN.md §15)
    integrity.save_npy(path, arr)


def _load_npy(path: str) -> np.ndarray:
    return integrity.load_npy(path)


def _load_npz(path: str) -> dict:
    return integrity.load_npz(path)


def _rng_state_jsonable(state: dict) -> dict:
    """numpy bit-generator state dicts are JSON-safe except for numpy
    integer leaves — normalize to built-in ints recursively."""
    def conv(x):
        if isinstance(x, dict):
            return {k: conv(v) for k, v in x.items()}
        if isinstance(x, (np.integer,)):
            return int(x)
        return x
    return conv(state)


class StreamingLDA:
    """Out-of-core model-parallel LDA over a sharded on-disk corpus.

    Same chain as ``ModelParallelLDA(corpus, ...)`` with the same seed —
    proven draw-for-draw by ``tests/test_stream_resume.py`` — but memory
    bounded by one resident block + one in-flight token group.
    """

    def __init__(self, corpus: "ShardedCorpus | str", workdir: str,
                 num_topics: int, num_workers: int,
                 alpha: float = 0.1, beta: float = 0.01, seed: int = 0,
                 sampler_mode: str = "scan", blocks_per_worker: int = 1,
                 data_parallel: int = 1,
                 table_lifetime: Optional[str] = None,
                 sampler_args: Optional[tuple] = None,
                 store: str = "dense"):
        from repro.core.engine.rounds import table_capable
        if isinstance(corpus, str):
            corpus = ShardedCorpus(corpus)
        self.workdir = workdir
        self.num_topics = int(num_topics)
        self.num_workers = int(num_workers)
        self.blocks_per_worker = int(blocks_per_worker)
        self.data_parallel = int(data_parallel)
        if self.blocks_per_worker < 1 or self.data_parallel < 1:
            raise ValueError("blocks_per_worker and data_parallel must "
                             "be >= 1")
        self.alpha = np.full(self.num_topics, alpha, np.float32) \
            if np.isscalar(alpha) else np.asarray(alpha, np.float32)
        self.alpha_scalar = float(alpha) if np.isscalar(alpha) else None
        self.beta = float(beta)
        self.seed = int(seed)
        self.sampler_mode = sampler_mode
        if table_lifetime is None:
            table_lifetime = ("iteration" if table_capable(sampler_mode)
                              else "round")
        if table_lifetime not in ("round", "iteration"):
            raise ValueError(f"unknown table_lifetime {table_lifetime!r}")
        if table_lifetime == "iteration" and not table_capable(sampler_mode):
            raise ValueError(
                "table_lifetime='iteration' needs a table-capable sampler "
                f"(the MH family), got {sampler_mode!r}")
        self.table_lifetime = table_lifetime
        self.vocab_size = corpus.vocab_size
        self.num_docs = corpus.num_docs
        self.num_tokens = corpus.num_tokens
        self.max_doc_len = corpus.max_doc_len
        self.vbeta = float(beta * self.vocab_size)
        if sampler_args is None:
            if sampler_mode in ("sparse", "sparse_pallas"):
                # same derivation as the engine facade (same corpus-level
                # max doc length, recorded in the corpus manifest), so the
                # identical jitted sampler instance runs both sides
                from repro.core.sparse_device import default_sparse_args
                sampler_args = default_sparse_args(self.num_topics,
                                                   int(self.max_doc_len))
            else:
                sampler_args = ()
        self.sampler_args = tuple(sampler_args)
        countstore.resolve_store(store)     # validate the kind early
        self.store_kind = store
        # head/tail split MUST match the sampler's (it is derived from
        # the same frozen counts); samplers without a wcap get the
        # module default, under which their store still round-trips
        self._store_wcap = int(dict(self.sampler_args).get(
            "wcap", countstore.DEFAULT_TAIL_WCAP))
        self._resolve_sampler()
        self.num_blocks = self.num_workers * self.blocks_per_worker
        self.num_shards = self.data_parallel * self.num_workers
        self.num_rounds = self.num_blocks
        self.partition = sched.partition_vocab(self.vocab_size,
                                               self.num_blocks)
        sched.validate_schedule(self.num_workers, self.blocks_per_worker)
        self._rng = np.random.default_rng(self.seed)
        if os.path.exists(self._p("state", PROGRESS_JSON)):
            raise ValueError(
                f"workdir {workdir!r} already holds a run; use "
                "StreamingLDA.resume() to continue it")
        self._init_from_corpus(corpus)

    # -- paths -------------------------------------------------------------
    def _p(self, *parts: str) -> str:
        return os.path.join(self.workdir, *parts)

    def _block_stem(self, blk: int, root: str = "state") -> str:
        # extensionless: the CountStore layer owns the artifact format
        # (.npy dense / .npz store-v2 record) and `countstore.load`
        # dispatches on whichever exists, so old dense workdirs and
        # cross-store resumes need no migration step
        return self._p(root, "blocks", f"block_{blk:05d}")

    def _load_block_store(self, blk: int,
                          root: str = "state") -> countstore.CountStore:
        return countstore.load(self._block_stem(blk, root))

    def _make_store(self, dense: np.ndarray) -> countstore.CountStore:
        return countstore.resolve_store(self.store_kind).from_dense(
            dense, wcap=self._store_wcap)

    def _empty_store(self) -> countstore.CountStore:
        return countstore.resolve_store(self.store_kind).empty(
            self.partition.block_size, self.num_topics,
            wcap=self._store_wcap)

    def _lay_path(self, g: int, b: int) -> str:
        return self._p("static", "rows", f"row{g:04d}_b{b:04d}.npz")

    def _z_path(self, g: int, b: int) -> str:
        return self._p("state", "rows", f"row{g:04d}_z_b{b:04d}.npy")

    def _cdk_path(self, g: int) -> str:
        return self._p("state", "rows", f"row{g:04d}_cdk.npy")

    # -- construction ------------------------------------------------------
    def _resolve_sampler(self) -> None:
        from repro.core.engine.rounds import (resolve_sampler,
                                              resolve_store_sampler,
                                              resolve_table_sampler)
        self._sampler_fn = (resolve_table_sampler(self.sampler_mode)
                            if self.table_lifetime == "iteration"
                            else resolve_sampler(self.sampler_mode,
                                                 self.sampler_args))
        # store-native form (zero-conversion lane path) when one exists
        # for this (sampler, store) pair; otherwise step() densifies the
        # resident block explicitly — surfaced by store_note()
        self._store_sampler_fn = None
        if self.store_kind != "dense" and self.table_lifetime == "round":
            self._store_sampler_fn = resolve_store_sampler(
                self.sampler_mode, self.store_kind, self.sampler_args)

    def store_note(self) -> Optional[str]:
        """One-line densification warning for the CLI config echo, or
        ``None`` when the store never converts (dense store, or a
        store-native sampler).  Satellite of DESIGN.md §16: densifying
        a compressed store is allowed but NEVER silent."""
        if self.store_kind == "dense" or self._store_sampler_fn is not None:
            return None
        vb, k = self.partition.block_size, self.num_topics
        mib = vb * k * 4 / 2**20
        return (f"store={self.store_kind!r}: sampler "
                f"{self.sampler_mode!r} has no store-native form — each "
                f"resident block densifies to [{vb}, {k}] "
                f"({mib:.1f} MiB) per round (zero-conversion samplers: "
                "sparse, sparse_pallas)")

    def _row_docs(self, g: int) -> np.ndarray:
        """Round-robin doc assignment — identical to `data/sharding.py`:
        grid row ``g`` owns global docs ``{g, g + R, ...}``."""
        return np.arange(g, self.num_docs, self.num_shards, dtype=np.int32)

    @property
    def dloc(self) -> int:
        return -(-self.num_docs // self.num_shards)

    @property
    def resident_block_rows(self) -> int:
        return self.partition.block_size

    def _init_from_corpus(self, corpus: ShardedCorpus) -> None:
        """Two streaming passes build the static layout and the initial
        chain state; peak memory is one disk shard plus one grid row's
        token slice (plus one ``[Vb, K]`` block during count scatter)."""
        r_, b_ = self.num_shards, self.num_blocks
        k, part = self.num_topics, self.partition
        for sub in ("static/rows", "state/rows", "state/blocks", "tables"):
            os.makedirs(self._p(*sub.split("/")), exist_ok=True)

        # pass 1: per-(row, block) token counts -> common capacity, and the
        # z0 chunk draws (engine-identical: integers(0, K, N) consumed in
        # stream order), parked next to their shard for pass 2
        counts = np.zeros((r_, b_), np.int64)
        for shard in corpus.iter_shards():
            z0c = self._rng.integers(0, k, size=shard.num_tokens) \
                .astype(np.int32)
            _save_npy(self._p("static", f"z0_shard{shard.index:05d}.npy"),
                      z0c)
            row = shard.doc % r_
            blk = part.block_of_word(shard.word)
            np.add.at(counts, (row, blk), 1)
        self.capacity = max(1, int(counts.max(initial=0)))

        # pass 2: per grid row, gather its token slice (global stream
        # order), build the inverted-index layout, scatter initial counts
        tok_start = np.zeros(corpus.num_shards + 1, np.int64)
        for i, entry in enumerate(corpus.meta["shards"]):
            tok_start[i + 1] = tok_start[i] + int(entry["num_tokens"])
        for g in range(r_):
            docs_g, words_g, z_g, tid_g = [], [], [], []
            for shard in corpus.iter_shards():
                m = (shard.doc % r_) == g
                docs_g.append(shard.doc[m])
                words_g.append(shard.word[m])
                z0c = _load_npy(
                    self._p("static", f"z0_shard{shard.index:05d}.npy"))
                z_g.append(z0c[m])
                tid_g.append(np.nonzero(m)[0].astype(np.int64)
                             + tok_start[shard.index])
            doc_glob = np.concatenate(docs_g) if docs_g \
                else np.zeros(0, np.int32)
            word_g = np.concatenate(words_g) if words_g \
                else np.zeros(0, np.int32)
            z_row = np.concatenate(z_g) if z_g else np.zeros(0, np.int32)
            tid_row = np.concatenate(tid_g) if tid_g \
                else np.zeros(0, np.int64)
            doc_local = ((doc_glob - g) // r_).astype(np.int32)
            idx = build_inverted_index(doc_local, word_g, part,
                                       self.capacity)
            cdk_g = np.zeros((self.dloc, k), np.int32)
            np.add.at(cdk_g, (doc_local, z_row), 1)
            _save_npy(self._cdk_path(g), cdk_g)
            mine = self._row_docs(g)
            doc_global = np.full(self.dloc, -1, np.int32)
            doc_global[:mine.shape[0]] = mine
            _save_npy(self._p("static", "rows", f"row{g:04d}_docs.npy"),
                      doc_global)
            for b in range(b_):
                msk = idx.mask[b]
                zlay = np.zeros(self.capacity, np.int32)
                zlay[msk] = z_row[idx.token_id[b][msk]]
                glob_tid = np.zeros(self.capacity, np.int64)
                glob_tid[msk] = tid_row[idx.token_id[b][msk]]
                integrity.save_npz(self._lay_path(g, b), doc=idx.doc[b],
                                   woff=idx.word_off[b], mask=msk,
                                   tid=glob_tid)
                _save_npy(self._z_path(g, b), zlay)
                # scatter this (row, block) group's initial counts into
                # the block store — one block (at its store's occupancy,
                # not [Vb, K]) in memory at a time
                stem = self._block_stem(b)
                blk_store = (countstore.load(stem)
                             if countstore.exists(stem)
                             else self._empty_store())
                woff_b = idx.word_off[b][msk]
                blk_store.apply_coo(woff_b, zlay[msk],
                                    np.ones(woff_b.shape[0], np.int64))
                blk_store.save(stem)
        for shard_entry in range(corpus.num_shards):
            z0p = self._p("static", f"z0_shard{shard_entry:05d}.npy")
            os.remove(z0p)
            os.remove(integrity.sidecar_path(z0p))

        ck = np.zeros(k, np.int64)
        for b in range(b_):
            ck += self._load_block_store(b).col_sums()
        _save_npy(self._p("state", "ck.npy"), ck)
        self.iteration_count = 0
        self._write_run_json()
        self._write_progress()

    def _write_run_json(self) -> None:
        cfg = {
            "format": "streaming-lda-v1",
            "num_topics": self.num_topics,
            "num_workers": self.num_workers,
            "blocks_per_worker": self.blocks_per_worker,
            "data_parallel": self.data_parallel,
            "sampler_mode": self.sampler_mode,
            "sampler_args": list(map(list, self.sampler_args)),
            "table_lifetime": self.table_lifetime,
            "alpha": self.alpha_scalar if self.alpha_scalar is not None
            else self.alpha.tolist(),
            "beta": self.beta,
            "seed": self.seed,
            "vocab_size": self.vocab_size,
            "num_docs": self.num_docs,
            "num_tokens": self.num_tokens,
            "max_doc_len": self.max_doc_len,
            "capacity": self.capacity,
            "store": self.store_kind,
            "store_wcap": self._store_wcap,
        }
        integrity.atomic_write_json(self._p(RUN_JSON), cfg, indent=1,
                                    checksum=True)

    def _write_progress(self) -> None:
        prog = {"iteration_count": self.iteration_count,
                "rng_state": _rng_state_jsonable(
                    self._rng.bit_generator.state)}
        integrity.atomic_write_json(self._p("state", PROGRESS_JSON), prog,
                                    checksum=True)

    # -- checkpoint / resume ----------------------------------------------
    def save_checkpoint(self) -> str:
        """Snapshot ``state/`` into ``ckpt/`` with an atomic directory
        swap.  Taken at an iteration boundary (the only place `step`
        returns control), where the traveling-table queue is empty and
        replicas agree — so the snapshot is sampler- and
        backend-agnostic."""
        tmp, final = self._p("ckpt.tmp"), self._p("ckpt")
        faults.fire("ckpt.begin", final)
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        shutil.copytree(self._p("state"), tmp)
        faults.fire("ckpt.tmp_copied", tmp)
        old = self._p("ckpt.old")
        if os.path.exists(final):
            if os.path.exists(old):     # debris from a kill after promote
                shutil.rmtree(old)
            os.rename(final, old)
            faults.fire("ckpt.old_moved", old)
        os.rename(tmp, final)
        faults.fire("ckpt.promoted", final)
        if os.path.exists(old):
            shutil.rmtree(old)
        return final

    @classmethod
    def resume(cls, workdir: str,
               store: Optional[str] = None) -> "StreamingLDA":
        """Reopen a run from its last :meth:`save_checkpoint`.  Restores
        ``ckpt/`` over ``state/`` (a kill mid-iteration leaves ``state/``
        partially advanced — the checkpoint is the consistent truth),
        then reloads config, rng bit-generator state, and iteration
        count; subsequent draws are bit-identical to a run that never
        stopped.

        ``store`` optionally MIGRATES the run to a different count-store
        kind: block files are re-encoded (exact integer round-trip, so
        the continued chain stays bitwise identical — pinned by
        tests/test_countstore.py) and run.json is rewritten.  Old
        pre-store workdirs carry no ``store`` field and default to
        ``dense``, which is exactly what their ``.npy`` blocks are."""
        with open(os.path.join(workdir, RUN_JSON)) as f:
            cfg = json.load(f)
        if cfg.get("format") != "streaming-lda-v1":
            raise ValueError(f"not a StreamingLDA workdir: {workdir!r}")
        ckpt = os.path.join(workdir, "ckpt")
        if not os.path.isdir(ckpt):
            old = os.path.join(workdir, "ckpt.old")
            if os.path.isdir(old):      # killed between the two renames
                os.rename(old, ckpt)
            else:
                raise ValueError(
                    f"no checkpoint under {workdir!r}; save_checkpoint() "
                    "must run before a kill to resume from")
        # validate every stamped artifact before trusting the checkpoint:
        # a bit-flipped block/row/progress file raises the integrity
        # taxonomy here instead of poisoning the resumed chain
        integrity.validate_tree(ckpt)
        alpha = cfg["alpha"]
        # constructed manually: the corpus-derived fields come from
        # run.json, not from a corpus scan
        self = cls.__new__(cls)
        self.workdir = workdir
        self.num_topics = int(cfg["num_topics"])
        self.num_workers = int(cfg["num_workers"])
        self.blocks_per_worker = int(cfg["blocks_per_worker"])
        self.data_parallel = int(cfg["data_parallel"])
        self.alpha = (np.full(self.num_topics, alpha, np.float32)
                      if np.isscalar(alpha)
                      else np.asarray(alpha, np.float32))
        self.alpha_scalar = float(alpha) if np.isscalar(alpha) else None
        self.beta = float(cfg["beta"])
        self.seed = int(cfg["seed"])
        self.sampler_mode = cfg["sampler_mode"]
        self.table_lifetime = cfg["table_lifetime"]
        self.vocab_size = int(cfg["vocab_size"])
        self.num_docs = int(cfg["num_docs"])
        self.num_tokens = int(cfg["num_tokens"])
        self.max_doc_len = int(cfg["max_doc_len"])
        self.capacity = int(cfg["capacity"])
        self.vbeta = float(self.beta * self.vocab_size)
        self.sampler_args = tuple(
            tuple(p) for p in cfg.get("sampler_args", []))
        self.store_kind = cfg.get("store", "dense")
        self._store_wcap = int(cfg.get(
            "store_wcap", dict(self.sampler_args).get(
                "wcap", countstore.DEFAULT_TAIL_WCAP)))
        self._resolve_sampler()
        self.num_blocks = self.num_workers * self.blocks_per_worker
        self.num_shards = self.data_parallel * self.num_workers
        self.num_rounds = self.num_blocks
        self.partition = sched.partition_vocab(self.vocab_size,
                                               self.num_blocks)
        state = os.path.join(workdir, "state")
        if os.path.exists(state):
            shutil.rmtree(state)
        shutil.copytree(ckpt, state)
        with open(self._p("state", PROGRESS_JSON)) as f:
            prog = json.load(f)
        self.iteration_count = int(prog["iteration_count"])
        self._rng = np.random.default_rng(self.seed)
        self._rng.bit_generator.state = prog["rng_state"]
        if store is not None and store != self.store_kind:
            self.set_store(store)
        return self

    def set_store(self, store: str) -> None:
        """Migrate the live run's blocks to count-store kind ``store``
        (the ``to_dense`` round-trip — exact, so the chain continues
        bitwise) and make it the kind for all subsequent writes."""
        countstore.resolve_store(store)
        if store == self.store_kind:
            return
        self.store_kind = store
        self._resolve_sampler()
        for b in range(self.num_blocks):
            st = self._load_block_store(b)
            if st.kind != store:
                self._make_store(st.to_dense()).save(self._block_stem(b))
        self._write_run_json()

    # -- stepping ----------------------------------------------------------
    def step(self) -> None:
        """One iteration = ``S·M`` rounds, round-major over the grid rows
        with frozen-per-round semantics — the serial transcript of the
        SPMD engine, with at most one block (plus its packed table) and
        one row/block token group in memory at a time."""
        import jax.numpy as jnp
        faults.fire("step", f"iter:{self.iteration_count},engine:streaming")
        m_, s_, d_ = (self.num_workers, self.blocks_per_worker,
                      self.data_parallel)
        k, cap = self.num_topics, self.capacity
        travel = self.table_lifetime == "iteration"
        alpha_j = jnp.asarray(self.alpha)
        beta_j = jnp.float32(self.beta)
        vbeta_j = jnp.float32(self.vbeta)
        if travel:
            from repro.core.mh import build_doc_tables
            # per-iteration doc tables from iteration-start cdk; word
            # tables are built lazily at each block's first residency
            for g in range(self.num_shards):
                dtab = np.asarray(build_doc_tables(
                    jnp.asarray(_load_npy(self._cdk_path(g))), alpha_j))
                _save_npy(self._p("tables", f"doc_g{g:04d}.npy"), dtab)
            for f in os.listdir(self._p("tables")):
                if f.startswith("word_"):
                    os.remove(self._p("tables", f))

        ck = _load_npy(self._p("state", "ck.npy"))
        for r in range(self.num_rounds):
            faults.fire("round", f"iter:{self.iteration_count},round:{r},")
            ck_frozen = ck.astype(np.int32)
            delta = np.zeros(k, np.int64)
            # engine-identical uniforms: random((B, R, cap)) consumed
            # round-major then row-major — drawn per round here so memory
            # stays one round's worth
            u_r = self._rng.random((self.num_shards, cap), np.float32)
            # process rows grouped by model position so each round's M
            # distinct blocks are loaded, updated by their D replicas, and
            # committed ONE AT A TIME (the memory bound); within a round
            # the tasks are independent given the frozen inputs, so the
            # regrouping cannot change any draw
            for m in range(m_):
                blk_id = sched.block_for(m, r, m_, s_)
                blk_store = self._load_block_store(blk_id)
                if self._store_sampler_fn is not None:
                    # STORE-NATIVE path (DESIGN.md §16): the sampler
                    # consumes the lane layout directly — no [Vb, K]
                    # buffer exists; the block fold is the store's exact
                    # integer token-delta apply at the round boundary
                    dev = blk_store.device_operands()
                    dev_j = tuple(jnp.asarray(dev[n]) for n in
                                  ("tail_topics", "tail_counts",
                                   "over_pad", "row_map"))
                    tok_w, tok_old, tok_new = [], [], []
                    for d in range(d_):
                        g = d * m_ + m
                        lay = _load_npz(self._lay_path(g, blk_id))
                        z = _load_npy(self._z_path(g, blk_id))
                        cdk = _load_npy(self._cdk_path(g))
                        out = self._store_sampler_fn(
                            jnp.asarray(cdk), *dev_j,
                            jnp.asarray(ck_frozen),
                            jnp.asarray(lay["doc"]),
                            jnp.asarray(lay["woff"]), jnp.asarray(z),
                            jnp.asarray(lay["mask"]),
                            jnp.asarray(u_r[g]), alpha_j, beta_j,
                            vbeta_j)
                        z_new = np.asarray(out[2])
                        _save_npy(self._cdk_path(g), np.asarray(out[0]))
                        _save_npy(self._z_path(g, blk_id), z_new)
                        delta += (np.asarray(out[1]).astype(np.int64)
                                  - ck_frozen)
                        msk = lay["mask"]
                        tok_w.append(lay["woff"][msk])
                        tok_old.append(z[msk])
                        tok_new.append(z_new[msk])
                    if tok_w:
                        blk_store.apply_token_delta(
                            np.concatenate(tok_w),
                            np.concatenate(tok_old),
                            np.concatenate(tok_new))
                    blk_store.save(self._block_stem(blk_id))
                    continue
                # dense-view path: DenseStore's to_dense IS the resident
                # array (free); a compressed store densifies here — an
                # EXPLICIT conversion, echoed by store_note()
                blk_frozen = blk_store.to_dense()
                blk_delta = np.zeros_like(blk_frozen)
                if travel:
                    wpath = self._p("tables", f"word_b{blk_id:04d}.npy")
                    if not os.path.exists(wpath):   # first residency
                        from repro.core.mh import build_word_tables
                        wtab = np.asarray(build_word_tables(
                            jnp.asarray(blk_frozen), beta_j))
                        _save_npy(wpath, wtab)
                    else:
                        wtab = _load_npy(wpath)
                for d in range(d_):
                    g = d * m_ + m
                    lay = _load_npz(self._lay_path(g, blk_id))
                    z = _load_npy(self._z_path(g, blk_id))
                    cdk = _load_npy(self._cdk_path(g))
                    args = (jnp.asarray(cdk), jnp.asarray(blk_frozen),
                            jnp.asarray(ck_frozen),
                            jnp.asarray(lay["doc"]),
                            jnp.asarray(lay["woff"]), jnp.asarray(z),
                            jnp.asarray(lay["mask"]),
                            jnp.asarray(u_r[g]), alpha_j, beta_j, vbeta_j)
                    if travel:
                        dtab = _load_npy(
                            self._p("tables", f"doc_g{g:04d}.npy"))
                        args += (jnp.asarray(wtab), jnp.asarray(dtab))
                    out = self._sampler_fn(*args)
                    _save_npy(self._cdk_path(g), np.asarray(out[0]))
                    _save_npy(self._z_path(g, blk_id), np.asarray(out[3]))
                    blk_delta += np.asarray(out[1]) - blk_frozen
                    delta += (np.asarray(out[2]).astype(np.int64)
                              - ck_frozen)
                self._make_store(blk_frozen + blk_delta).save(
                    self._block_stem(blk_id))
            ck = ck + delta
            _save_npy(self._p("state", "ck.npy"), ck)
        self.iteration_count += 1
        self._write_progress()

    def run(self, num_iterations: int,
            checkpoint_every: int = 0) -> List[dict]:
        history = []
        for i in range(num_iterations):
            self.step()
            history.append({"iteration": self.iteration_count})
            if checkpoint_every and (i + 1) % checkpoint_every == 0:
                self.save_checkpoint()
        return history

    # -- observation -------------------------------------------------------
    def memory_report(self, scan_store: bool = True) -> dict:
        """Resident-footprint report.  ``resident_block_bytes`` /
        ``total_model_bytes`` stay the DENSE formulas (the paper's
        capacity denominator, and what a densify would cost); the
        ``store_*`` keys report what the block store ACTUALLY occupies —
        max-over-blocks resident bytes plus aggregated head/tail
        occupancy and overflow-row counters (``scan_store=False`` skips
        the block scan for cheap formula-only calls)."""
        vb, k = self.partition.block_size, self.num_topics
        rep = {
            "num_workers": self.num_workers,
            "blocks_per_worker": self.blocks_per_worker,
            "data_parallel": self.data_parallel,
            "num_blocks": self.num_blocks,
            "resident_block_shape": (vb, k),
            "resident_block_bytes": vb * k * 4,
            "total_model_bytes": self.vocab_size * k * 4,
            "row_group_bytes": self.capacity * 4 * 4,
            "row_cdk_bytes": self.dloc * k * 4,
            "store": self.store_kind,
        }
        if scan_store:
            agg = {"head_rows": 0, "tail_rows": 0, "overflow_rows": 0,
                   "tail_nnz": 0}
            resident = total = 0
            for b in range(self.num_blocks):
                occ = self._load_block_store(b).occupancy()
                for key in agg:
                    agg[key] += occ[key]
                resident = max(resident, occ["nbytes_resident"])
                total += occ["nbytes_resident"]
            rep["store_occupancy"] = agg
            rep["resident_store_bytes"] = resident
            rep["total_store_bytes"] = total
        return rep

    def gather_counts(self):
        """Reassemble the global model — materializes ``[V, K]``; for
        tests and small runs (use :meth:`save_snapshot_sharded` at
        scale)."""
        from repro.core.counts import CountState
        import jax.numpy as jnp
        vb, k = self.partition.block_size, self.num_topics
        ckt = np.zeros((self.partition.padded_vocab, k), np.int32)
        for b in range(self.num_blocks):
            ckt[b * vb:(b + 1) * vb] = self._load_block_store(b).to_dense()
        ckt = ckt[:self.vocab_size]
        cdk = np.zeros((self.num_docs, k), np.int32)
        for g in range(self.num_shards):
            docs = _load_npy(
                self._p("static", "rows", f"row{g:04d}_docs.npy"))
            real = docs >= 0
            cdk[docs[real]] = _load_npy(self._cdk_path(g))[:real.sum()]
        ck = ckt.sum(axis=0).astype(np.int32)
        return CountState(jnp.asarray(cdk), jnp.asarray(ckt),
                          jnp.asarray(ck))

    def assignments(self) -> np.ndarray:
        """Current z in original token order (streamed, O(N) output)."""
        z = np.zeros(self.num_tokens, np.int32)
        for g in range(self.num_shards):
            for b in range(self.num_blocks):
                lay = _load_npz(self._lay_path(g, b))
                msk = lay["mask"]
                z[lay["tid"][msk]] = _load_npy(self._z_path(g, b))[msk]
        return z

    def log_likelihood(self) -> float:
        from repro.core.likelihood import (doc_log_likelihood,
                                           word_log_likelihood)
        state = self.gather_counts()
        return float(word_log_likelihood(state.ckt, state.ck, self.beta)
                     + doc_log_likelihood(state.cdk, self.alpha))

    def snapshot(self, build_tables: bool = False):
        """In-memory frozen serving snapshot (small runs)."""
        from repro.core.infer import ModelSnapshot
        state = self.gather_counts()
        return ModelSnapshot.from_counts(
            np.asarray(state.ckt), np.asarray(state.ck), self.alpha,
            self.beta, build_tables=build_tables)

    def save_snapshot_sharded(self, out_dir: str) -> str:
        """Streaming snapshot export: one block store at a time is copied
        into a sharded snapshot directory (`core/infer.py`
        ``load_snapshot_rows`` serves from it row-restricted) — the full
        ``[V, K]`` model is never materialized.  A dense-store run writes
        the unchanged ``sharded-snapshot-v1`` layout (plain ``.npy``
        blocks); a compressed store exports its own records under format
        v2 with the store kind stamped in meta.json."""
        os.makedirs(out_dir, exist_ok=True)
        ck = np.zeros(self.num_topics, np.int64)
        for b in range(self.num_blocks):
            st = self._load_block_store(b)
            st.save(os.path.join(out_dir, f"block_{b:05d}"))
            ck += st.col_sums()
        integrity.save_npy(os.path.join(out_dir, "ck.npy"),
                           ck.astype(np.int64))
        meta = {
            "format": ("sharded-snapshot-v1"
                       if self.store_kind == "dense"
                       else "sharded-snapshot-v2"),
            "store": self.store_kind,
            "vocab_size": self.vocab_size,
            "num_topics": self.num_topics,
            "num_blocks": self.num_blocks,
            "block_size": self.partition.block_size,
            "alpha": (self.alpha_scalar if self.alpha_scalar is not None
                      else self.alpha.tolist()),
            "beta": self.beta,
            "iteration": self.iteration_count,
        }
        # meta.json is published LAST and atomically: its presence is the
        # completeness signal the serve-side watcher keys on (§15)
        integrity.atomic_write_json(os.path.join(out_dir, "meta.json"),
                                    meta, indent=1, checksum=True)
        return out_dir
