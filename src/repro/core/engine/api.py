"""Public engine facade: :class:`ModelParallelLDA` (the paper's full
system, generalized to ``S`` blocks per worker and ``D`` data replicas —
DESIGN.md §2–§3, §8).

Example::

    lda = ModelParallelLDA(corpus, num_topics=64, num_workers=8,
                           blocks_per_worker=4)   # 32-block pipeline
    history = lda.run(num_iterations=50)
    state = lda.gather_counts()

    hybrid = ModelParallelLDA(corpus, num_topics=64, num_workers=8,
                              data_parallel=4)    # 4 × 8 (data, model) grid
    hybrid.run(num_iterations=50)

``blocks_per_worker`` (``S``) is the model-capacity lever: the resident
word-topic block per worker is ``ceil(V / (S·M)) × K`` rows, so growing
``S`` shrinks the per-worker resident model without adding workers —
the paper's "model size exceeds any single node's RAM" claim as a tunable.

``sampler_mode`` selects the per-block sampler from the `rounds.py`
registry: the exact ``scan``, the word-frozen ``batched``/``pallas``
pair, or the O(1) alias-table MH pair ``mh``/``mh_pallas`` (DESIGN.md
§9).  The MH modes target the same collapsed posterior but are only
distribution-equal to the exact chain, so their validation is the
statistical suite `tests/test_mh_stats.py` plus a draw-for-draw host
oracle replay (`kvstore.HostModelParallelLDA(sampler="mh")`).

``table_lifetime`` governs how long MH proposal tables live (DESIGN.md
§10): ``"iteration"`` (the default for the MH family) builds each
block's word table once per iteration at its first residency and rotates
the packed table with the block, with doc tables built once from
iteration-start counts — amortizing the O((Vb + D_loc)·K) build by a
factor of ``S·M``; ``"round"`` is the original rebuild-every-round
schedule (the A/B baseline).  The chain stays exact either way — the
eq.-(1) acceptance corrects arbitrarily stale proposals — and the host
oracle mirrors whichever schedule is selected, so replay stays bitwise.

``track_error=False`` skips the per-round Fig-3 drift statistic (the
``delta_error()`` history) — benchmarks use it to keep the hot path free
of an unconsumed [R, K]-wide reduction per round.

``data_parallel`` (``D``) is the throughput lever: documents shard
``D·M`` ways over a 2D ``(data, model)`` grid while each replica keeps a
copy of the block pipeline, reconciled by a per-round delta psum along
``data`` (the AD-LDA all-reduce confined to the resident slice).  The
parallelization error stays confined to ``{C_k}`` within a round —
doc-topic counts are exact by construction, word-topic counts exact at
every round boundary — which is the quantity the paper measures in
Figs 2–4.  ``D = 1`` is bit-identical to the original 1D engine
(``engine/reference.py``); ``M = 1`` degenerates to AD-LDA
(``core/data_parallel.py``'s staleness model with ``S`` vocabulary-sliced
sync points per iteration).
"""
from __future__ import annotations

import json
import os
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import tracing
from repro.core.counts import CountState
from repro.core.engine import state as engine_state
from repro.core.engine.backends import (iteration_vmap,
                                        make_shard_map_iteration)
from repro.core.engine.rounds import resolve_sampler, table_capable
from repro.core.likelihood import doc_log_likelihood, word_log_likelihood
from repro.data.corpus import Corpus


class ModelParallelLDA:
    """Model-parallel LDA trainer over an ``S·M``-block pipeline."""

    def __init__(self, corpus: Corpus, num_topics: int, num_workers: int,
                 alpha: float | np.ndarray = 0.1, beta: float = 0.01,
                 seed: int = 0, sampler_mode: str = "scan",
                 sync_ck: bool = True, backend: str = "vmap",
                 mesh: Optional[Mesh] = None, axis: str = "w",
                 blocks_per_worker: int = 1, data_parallel: int = 1,
                 data_axis: str = "data",
                 table_lifetime: Optional[str] = None,
                 track_error: bool = True,
                 sampler_args: Optional[tuple] = None,
                 store: str = "dense"):
        corpus.validate()
        if blocks_per_worker < 1:
            raise ValueError(
                f"blocks_per_worker must be >= 1, got {blocks_per_worker}")
        if data_parallel < 1:
            raise ValueError(
                f"data_parallel must be >= 1, got {data_parallel}")
        if data_parallel > 1 and not sync_ck:
            raise ValueError(
                "data_parallel > 1 requires sync_ck=True: replica copies "
                "of a block are only well-defined between round "
                "boundaries (same restriction as the host oracle)")
        self.corpus = corpus
        self.num_topics = int(num_topics)
        self.num_workers = int(num_workers)
        self.blocks_per_worker = int(blocks_per_worker)
        self.data_parallel = int(data_parallel)
        self.alpha = jnp.full((num_topics,), alpha, jnp.float32) \
            if np.isscalar(alpha) else jnp.asarray(alpha, jnp.float32)
        self.beta = float(beta)
        self.vbeta = float(beta * corpus.vocab_size)
        if sampler_args is None:
            if sampler_mode in ("sparse", "sparse_pallas"):
                # the sparse family needs its static lane capacities: dcap
                # must bound nnz(cdk row) ≤ min(K, longest doc); the host
                # oracle derives the SAME config from the same corpus so
                # replays run the identical jitted sampler.
                from repro.core.sparse_device import default_sparse_args
                sampler_args = default_sparse_args(
                    num_topics, int(corpus.doc_lengths().max()))
            else:
                sampler_args = ()
        self.sampler_args = tuple(sampler_args)
        resolve_sampler(sampler_mode, self.sampler_args)  # fail fast
        self.sampler_mode = sampler_mode
        from repro.core.engine import countstore
        countstore.resolve_store(store)                   # fail fast
        self.store_kind = store
        self._store_wcap = int(dict(self.sampler_args).get(
            "wcap", countstore.DEFAULT_TAIL_WCAP))
        if table_lifetime is None:
            # the amortized schedule is the default wherever it applies
            table_lifetime = ("iteration" if table_capable(sampler_mode)
                              else "round")
        if table_lifetime not in ("round", "iteration"):
            raise ValueError(
                f"unknown table_lifetime {table_lifetime!r}; "
                "expected 'round' or 'iteration'")
        if table_lifetime == "iteration" and not table_capable(sampler_mode):
            raise ValueError(
                f"table_lifetime='iteration' needs a table-capable "
                f"sampler (the MH family), got {sampler_mode!r}")
        self.table_lifetime = table_lifetime
        self.track_error = bool(track_error)
        self.sync_ck = bool(sync_ck)
        self.backend = backend
        self.axis = axis
        self.data_axis = data_axis
        self._rng = np.random.default_rng(seed)
        self._build()
        if backend == "shard_map":
            # 2D (data, model) layout when D > 1 or the caller hands us a
            # mesh that already carries the data axis (lets tests exercise
            # the 2D code path at D = 1).
            use_2d = (self.data_parallel > 1
                      or (mesh is not None and data_axis in mesh.axis_names))
            need = self.num_shards
            if mesh is None:
                if len(jax.devices()) < need:
                    raise ValueError(
                        f"shard_map backend needs {need} devices, "
                        f"have {len(jax.devices())}")
                if use_2d:
                    mesh = Mesh(
                        np.array(jax.devices()[:need]).reshape(
                            self.data_parallel, self.num_workers),
                        (data_axis, axis))
                else:
                    mesh = Mesh(np.array(jax.devices()[:need]), (axis,))
            else:
                # a mismatched mesh would silently drop grid rows (each
                # device keeps only its first local row) — reject early
                want = {axis: self.num_workers}
                if use_2d:
                    want[data_axis] = self.data_parallel
                got = dict(mesh.shape)
                if got != want:
                    raise ValueError(
                        f"mesh axes {got} do not match the "
                        f"(data_parallel={self.data_parallel}, "
                        f"num_workers={self.num_workers}) grid; expected "
                        f"exactly {want}")
            self.mesh = mesh
            self._iter_fn = make_shard_map_iteration(
                mesh, axis, sampler_mode, sync_ck,
                data_axis=data_axis if use_2d else None,
                table_lifetime=self.table_lifetime,
                track_error=self.track_error,
                sampler_args=self.sampler_args)
        else:
            self.mesh = None
            self._iter_fn = None

    # -- construction ------------------------------------------------------
    def _build(self) -> None:
        self.layout = engine_state.build_layout(
            self.corpus, self.num_workers, self.blocks_per_worker,
            self.data_parallel)
        z0 = self._rng.integers(
            0, self.num_topics, size=self.corpus.num_tokens).astype(np.int32)
        self.z_init = z0
        self.state = engine_state.init_state(self.layout, self.num_topics,
                                             z0)
        self.iteration_count = 0

    # -- layout views (kept as attributes of the facade) -------------------
    @property
    def partition(self):
        return self.layout.partition

    @property
    def shards(self):
        return self.layout.shards

    @property
    def indexes(self):
        return self.layout.indexes

    @property
    def capacity(self) -> int:
        return self.layout.capacity

    @property
    def doc(self):
        return self.layout.doc

    @property
    def woff(self):
        return self.layout.woff

    @property
    def mask(self):
        return self.layout.mask

    @property
    def num_shards(self) -> int:
        """Worker-grid rows ``R = D·M`` (== ``M`` at ``data_parallel=1``)."""
        return self.layout.num_shards

    @property
    def num_blocks(self) -> int:
        return self.layout.num_blocks

    @property
    def num_rounds(self) -> int:
        return self.layout.num_rounds

    @property
    def resident_block_rows(self) -> int:
        """``ceil(V / (S·M))`` — rows of the block a worker actively holds."""
        return self.layout.resident_block_rows

    def memory_report(self) -> dict:
        """Resident-vs-total model bytes (the paper's capacity claim),
        extended with the hybrid grid: the model is replicated ``D`` times
        (one copy per data replica, sharded over its ``M`` workers), so
        distributed bytes grow with ``D`` while the per-worker resident
        block stays ``ceil(V/(S·M)) × K`` — the two levers are orthogonal.
        """
        k = self.num_topics
        vb = self.resident_block_rows
        rep = {
            "num_workers": self.num_workers,
            "blocks_per_worker": self.blocks_per_worker,
            "data_parallel": self.data_parallel,
            "num_shards": self.num_shards,
            "num_blocks": self.num_blocks,
            "resident_block_shape": (vb, k),
            "resident_block_bytes": vb * k * 4,
            "parked_bytes_per_worker": (self.blocks_per_worker - 1)
            * vb * k * 4,
            "total_model_bytes": self.corpus.vocab_size * k * 4,
            "replica_model_bytes": self.num_blocks * vb * k * 4,
            "distributed_model_bytes": self.data_parallel
            * self.num_blocks * vb * k * 4,
            "store": self.store_kind,
        }
        if self.store_kind != "dense":
            # at-rest occupancy of the current chain under the selected
            # store (what a checkpoint of this state occupies)
            stores = engine_state.ckt_to_stores(
                np.asarray(self.state.ckt), self.store_kind,
                self._store_wcap)
            agg = {"head_rows": 0, "tail_rows": 0, "overflow_rows": 0,
                   "tail_nnz": 0}
            total = 0
            for st in stores:
                occ = st.occupancy()
                for key in agg:
                    agg[key] += occ[key]
                total += occ["nbytes_resident"]
            rep["store_occupancy"] = agg
            rep["total_store_bytes"] = total
        return rep

    def store_note(self) -> Optional[str]:
        """Densification note for the CLI config echo (DESIGN.md §16), or
        ``None`` for the dense default.  The in-memory engine's DEVICE
        chain is always dense — jit/donation/ppermute need static shapes
        — so a compressed store here governs the AT-REST artifacts
        (checkpoints) and is decoded to the dense device state on resume;
        the resident-memory win lives in the streaming engine."""
        if self.store_kind == "dense":
            return None
        vb, k = self.resident_block_rows, self.num_topics
        mib = self.num_blocks * vb * k * 4 / 2**20
        return (f"store={self.store_kind!r}: in-memory engine computes "
                f"on the dense device chain ({mib:.1f} MiB resident); "
                f"{self.store_kind!r} encoding applies to checkpoints "
                "at rest (use the streaming engine + sparse family for "
                "a compressed resident block)")

    # -- stepping ----------------------------------------------------------
    def _uniforms(self) -> jax.Array:
        b, r, cap = self.num_rounds, self.num_shards, self.capacity
        with tracing.span(tracing.TRAIN_UNIFORMS):
            # [rounds, rows, T]
            return jnp.asarray(self._rng.random((b, r, cap), np.float32))

    def step(self) -> None:
        """Run one iteration (= S·M rounds, every token sampled once)."""
        from repro.core import faults
        faults.fire("step", f"iter:{self.iteration_count},engine:mp")
        u = self._uniforms()
        with tracing.span(tracing.TRAIN_DISPATCH,
                          iteration=self.iteration_count):
            if self.backend == "vmap":
                self.state, errs = iteration_vmap(
                    self.state, u, self.doc, self.woff, self.mask,
                    self.alpha, jnp.float32(self.beta),
                    jnp.float32(self.vbeta),
                    sampler_mode=self.sampler_mode, sync_ck=self.sync_ck,
                    data_parallel=self.data_parallel,
                    table_lifetime=self.table_lifetime,
                    track_error=self.track_error,
                    sampler_args=self.sampler_args)
            else:
                s = self.state
                out = self._iter_fn(
                    s.cdk, s.ckt, s.block_id, s.ck_synced, s.ck_local, s.z,
                    jnp.swapaxes(u, 0, 1), self.doc, self.woff, self.mask,
                    self.alpha, jnp.float32(self.beta),
                    jnp.float32(self.vbeta))
                self.state = engine_state.MPState(*out[:6])
                errs = out[6]
        self.round_errors = (np.asarray(errs).reshape(-1)
                             if self.track_error else np.zeros(0))
        self.iteration_count += 1

    def run(self, num_iterations: int,
            callback: Optional[Callable[[int, "ModelParallelLDA"],
                                        None]] = None,
            eval_every: int = 1) -> List[dict]:
        history = []
        for i in range(num_iterations):
            self.step()
            if (i + 1) % eval_every == 0:
                history.append({"iteration": self.iteration_count,
                                "log_likelihood": self.log_likelihood()})
            if callback is not None:
                callback(i, self)
        return history

    # -- checkpoint / resume -----------------------------------------------
    CKPT_FORMAT = "mp-lda-ckpt-v1"
    CKPT_FORMAT_V2 = "mp-lda-ckpt-v2"

    def save_checkpoint(self, path: str) -> str:
        """Serialize the full chain state to one ``.npz``: the six
        ``MPState`` arrays (the slot queues ``ckt``/``block_id`` included),
        the host rng's bit-generator state, the iteration count, and a
        config echo.  Taken at an iteration boundary — the only place
        ``step()`` returns control — where the traveling-table queue is
        empty (tables are iteration-local derived state, DESIGN.md §10)
        and ``ck_synced`` is reconciled, so nothing sampler- or
        backend-specific needs saving: a checkpoint written by the vmap
        backend resumes bit-exactly on shard_map and vice versa.

        The write is atomic (temp file + ``os.replace``), so a kill during
        checkpointing leaves either the old file or the new one, never a
        torn state.

        Format versioning (DESIGN.md §16): a dense-store engine writes
        the bitwise-frozen v1 record (``ckt`` as one dense array); a
        compressed store writes v2, where the slot queue is encoded as
        per-slot ``store-v2`` CountStore records.  :meth:`resume` reads
        both, and either decodes to the identical dense device state —
        cross-store resume is bitwise."""
        from repro.data.corpus import npz_stem
        s = self.state
        cfg = {
            "format": (self.CKPT_FORMAT if self.store_kind == "dense"
                       else self.CKPT_FORMAT_V2),
            "store": self.store_kind,
            "store_wcap": self._store_wcap,
            "num_topics": self.num_topics,
            "num_workers": self.num_workers,
            "blocks_per_worker": self.blocks_per_worker,
            "data_parallel": self.data_parallel,
            "sampler_mode": self.sampler_mode,
            "sampler_args": [list(p) for p in self.sampler_args],
            "table_lifetime": self.table_lifetime,
            "sync_ck": self.sync_ck,
            "alpha": np.asarray(self.alpha, np.float32).tolist(),
            "beta": self.beta,
            "iteration_count": self.iteration_count,
            # corpus fingerprint: resume re-derives the static layout from
            # the corpus, so the wrong corpus must be rejected loudly
            "num_tokens": self.corpus.num_tokens,
            "vocab_size": self.corpus.vocab_size,
            "num_docs": self.corpus.num_docs,
        }
        from repro.core import faults
        from repro.data import integrity
        rng_state = self._rng.bit_generator.state
        stem = npz_stem(path)
        os.makedirs(os.path.dirname(stem) or ".", exist_ok=True)
        final = stem + ".npz"
        faults.fire("mp_ckpt.begin", final)
        # atomic + crc32-sidecar publish (DESIGN.md §15): integrity.save_npz
        # writes a temp file, fsyncs, os.replace-s, then stamps <path>.sum
        # — its npz.tmp_written fire point plus mp_ckpt.begin/promoted here
        # bracket every instant the kill-during-checkpoint tests target
        arrays = dict(
            cdk=np.asarray(s.cdk),
            block_id=np.asarray(s.block_id),
            ck_synced=np.asarray(s.ck_synced),
            ck_local=np.asarray(s.ck_local), z=np.asarray(s.z),
            config=np.frombuffer(
                json.dumps(cfg).encode(), np.uint8),
            rng_state=np.frombuffer(
                json.dumps(rng_state).encode(), np.uint8))
        if self.store_kind == "dense":
            arrays["ckt"] = np.asarray(s.ckt)
        else:
            # v2: the slot queue as per-slot CountStore records
            stores = engine_state.ckt_to_stores(
                np.asarray(s.ckt), self.store_kind, self._store_wcap)
            aux_list = []
            for i, st in enumerate(stores):
                aux, arrs = st.pack()
                aux_list.append(aux)
                for name, arr in arrs.items():
                    arrays[f"store{i}_{name}"] = arr
            arrays["store_aux"] = np.frombuffer(
                json.dumps(aux_list).encode(), np.uint8)
        integrity.save_npz(final, **arrays)
        faults.fire("mp_ckpt.promoted", final)
        return final

    @classmethod
    def resume(cls, corpus: Corpus, path: str, backend: str = "vmap",
               mesh: Optional[Mesh] = None, axis: str = "w",
               data_axis: str = "data",
               track_error: bool = True,
               store: Optional[str] = None) -> "ModelParallelLDA":
        """Rebuild a trainer from :meth:`save_checkpoint` output.  The
        geometry, sampler, and hyperparameters come from the checkpoint's
        config echo; the backend is the caller's choice (checkpoints are
        backend-agnostic).  The restored run is draw-for-draw identical
        to one that never stopped: the static layout is a pure function
        of ``(corpus, M, S, D)``, the chain state is restored bitwise,
        and the rng continues from the saved bit-generator state.

        Both checkpoint formats load: v1 stores ``ckt`` dense, v2 as
        per-slot CountStore records — either decodes to the identical
        device state (integer round-trip), so resuming a v2 checkpoint
        continues the v1 chain bitwise and vice versa.  ``store``
        overrides the checkpoint's store kind for the resumed trainer
        (``None`` keeps it); the override only changes how FUTURE
        checkpoints are encoded, never the chain."""
        from repro.data import integrity
        from repro.data.corpus import npz_stem
        from repro.core.engine import countstore
        stem = npz_stem(path)
        # validated load: a bit-flipped or torn checkpoint raises the
        # integrity taxonomy here instead of np.load's zip errors (or
        # silently-decoded garbage) poisoning the resumed chain
        data = integrity.load_npz(stem + ".npz")
        try:
            cfg = json.loads(bytes(data["config"]).decode())
            rng_state = json.loads(bytes(data["rng_state"]).decode())
            arrays = {k: np.asarray(data[k]) for k in
                      ("cdk", "block_id", "ck_synced",
                       "ck_local", "z")}
        except KeyError as e:
            raise ValueError(
                f"{stem}.npz is not an engine checkpoint: "
                f"missing {e}") from e
        fmt = cfg.get("format")
        if fmt not in (cls.CKPT_FORMAT, cls.CKPT_FORMAT_V2):
            raise ValueError(
                f"unknown checkpoint format {fmt!r} in {stem}.npz; "
                f"expected {cls.CKPT_FORMAT!r} or {cls.CKPT_FORMAT_V2!r}")
        if fmt == cls.CKPT_FORMAT:
            arrays["ckt"] = np.asarray(data["ckt"])
        else:
            aux_list = json.loads(bytes(data["store_aux"]).decode())
            keys = list(data.keys())
            stores = []
            for i, aux in enumerate(aux_list):
                pre = f"store{i}_"
                arrs = {k[len(pre):]: np.asarray(data[k])
                        for k in keys if k.startswith(pre)}
                stores.append(countstore.unpack_record(aux, arrs))
            r = int(cfg["data_parallel"]) * int(cfg["num_workers"])
            arrays["ckt"] = engine_state.ckt_from_stores(
                stores, r, int(cfg["blocks_per_worker"]))
        for key in ("num_tokens", "vocab_size", "num_docs"):
            if int(cfg[key]) != int(getattr(corpus, key)):
                raise ValueError(
                    f"corpus does not match checkpoint: {key} is "
                    f"{getattr(corpus, key)}, checkpoint has {cfg[key]}")
        lda = cls(corpus, num_topics=cfg["num_topics"],
                  num_workers=cfg["num_workers"],
                  alpha=np.asarray(cfg["alpha"], np.float32),
                  beta=cfg["beta"],
                  sampler_mode=cfg["sampler_mode"],
                  sync_ck=cfg["sync_ck"], backend=backend, mesh=mesh,
                  axis=axis, blocks_per_worker=cfg["blocks_per_worker"],
                  data_parallel=cfg["data_parallel"],
                  data_axis=data_axis,
                  table_lifetime=cfg["table_lifetime"],
                  track_error=track_error,
                  sampler_args=tuple(
                      tuple(p) for p in cfg["sampler_args"]),
                  store=(store if store is not None
                         else cfg.get("store", "dense")))
        lda.state = engine_state.MPState(
            cdk=jnp.asarray(arrays["cdk"]),
            ckt=jnp.asarray(arrays["ckt"]),
            block_id=jnp.asarray(arrays["block_id"]),
            ck_synced=jnp.asarray(arrays["ck_synced"]),
            ck_local=jnp.asarray(arrays["ck_local"]),
            z=jnp.asarray(arrays["z"]))
        lda._rng.bit_generator.state = rng_state
        lda.iteration_count = int(cfg["iteration_count"])
        return lda

    # -- observation -------------------------------------------------------
    def gather_counts(self) -> CountState:
        """Reassemble the global model (the KV-store "dump")."""
        return engine_state.gather_counts(self.layout, self.state,
                                          self.num_topics)

    def snapshot(self, build_tables: bool = False):
        """Export the frozen serving snapshot (DESIGN.md §11): the
        reassembled ``C_k^t``/``C_k`` blocks plus — built once per
        snapshot, lazily unless ``build_tables`` — the packed per-word
        alias tables (`alias.pack_tables` layout) that make frozen-model
        MH fold-in O(1) per query token.  The export is taken at an
        iteration boundary, where every replica's block copies agree, so
        snapshots are backend- and geometry-independent for the same
        chain (the fold-in oracle tests pin this at several (D, M, S)).
        """
        from repro.core.infer import ModelSnapshot
        state = self.gather_counts()
        return ModelSnapshot.from_counts(
            np.asarray(state.ckt), np.asarray(state.ck),
            np.asarray(self.alpha), self.beta, build_tables=build_tables)

    def assignments(self) -> np.ndarray:
        """Current z in original token order."""
        return engine_state.gather_assignments(self.layout, self.state)

    def log_likelihood(self) -> float:
        state = self.gather_counts()
        lw = word_log_likelihood(state.ckt, state.ck, self.beta)
        ld = doc_log_likelihood(state.cdk, self.alpha)
        return float(lw + ld)

    def delta_error(self) -> float:
        """Mean pre-sync Δ_{r,i} over the rounds of the last iteration
        (paper Fig 3).  Falls back to the current post-sync drift if no
        iteration has run yet."""
        errs = getattr(self, "round_errors", None)
        if errs is not None and errs.size:
            return float(errs.mean())
        from repro.core.metrics import delta_error
        return delta_error(self.state.true_ck(),
                           self.state.local_ck_views())
