"""Held-out (fold-in) inference against a frozen model snapshot.

Training (PRs 1–4) produces the collapsed counts ``{C_k^t, C_k}``; this
module is the SERVING half the north-star asks for: given a frozen
snapshot of those counts, infer the topic mixture ``θ̂`` of documents the
trainer never saw (Peacock's "online inference" stage; Hou et al. 2014).
Fold-in runs the same collapsed Gibbs/MH machinery as training but
updates ONLY the query document's ``C_d^k`` — the model counts stay
frozen, which changes the systems story completely (DESIGN.md §11):

* **no reconciliation** — queries never write shared state, so a query
  batch shards embarrassingly along the ``data`` axis: no block ring, no
  delta psum, no ``C_k`` sync.  The per-doc sweep is a ``vmap`` here and
  would be a pure data-parallel ``shard_map`` at scale.
* **alias tables build once per snapshot** — LightLDA notes frozen-model
  inference is the ideal case for alias proposals: ``q_w ∝ C_k^t + β``
  is static, so the per-word tables (`core/alias.py`, packed layout) are
  built once per :class:`ModelSnapshot` and amortize over EVERY query
  token served from it, not just one round's.
* **the model stays on the device** — the snapshot arrays the sweeps
  read are placed on the device once per snapshot
  (:meth:`ModelSnapshot.device_operands`); a call converts only its
  query's arrays.
* **replayable** — uniforms and initial assignments are drawn externally
  (same convention as the trainer), so a batched device fold-in is
  replayed draw-for-draw by the serial host oracle
  (`kvstore.fold_in_oracle`): the jitted per-doc kernel for the exact
  ``scan`` sampler, a pure-numpy mirror for the MH family.

Two samplers:

* ``scan`` — exact serial CGS per query doc over the frozen word term
  ``φ̂ᵀ = (C_k^t + β)/(C_k + Vβ)`` (one `lax.scan`, vmapped over docs);
* ``mh`` / ``mh_pallas`` — the O(1) alias-table MH cycle against the
  snapshot's static word tables plus per-sweep doc tables, through the
  SAME table-aware samplers the trainer registers (`engine/rounds.py`),
  so the serving path inherits the trainer's bit-exactness guarantees.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.mh import DEFAULT_MH_CYCLES, build_doc_tables
from repro.core.sampler import sample_from_mass

# Gibbs/MH sweeps over the estimation half of a query doc.  Fold-in
# burn-in is short because only D_loc = 1 rows of state mix.
DEFAULT_FOLD_IN_SWEEPS = 5

# the snapshot arrays each fold-in family's sweeps take, in argument order
RESIDENT_OPERANDS = {
    "scan": ("word_term", "alpha"),
    "sparse": ("word_term", "xcs", "sx"),
    "mh": ("ckt", "ck", "word_tables", "alpha", "beta", "vbeta"),
}


def fold_in_family(sampler: str) -> str:
    """The fold-in sweeps ``sampler`` runs: ``"scan"``, ``"sparse"``
    (both sparse names) or ``"mh"`` (any table-capable registry
    sampler)."""
    if sampler == "scan":
        return "scan"
    if sampler in ("sparse", "sparse_pallas"):
        return "sparse"
    from repro.core.engine.rounds import table_capable
    if table_capable(sampler):
        return "mh"
    raise ValueError(
        f"unknown fold-in sampler {sampler!r}; expected 'scan', "
        "'sparse'/'sparse_pallas', or a table-capable registry "
        "sampler (the MH family)")


# ---------------------------------------------------------------------------
# Frozen model snapshot (the serving export)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ModelSnapshot:
    """Frozen counts + once-per-snapshot alias tables (DESIGN.md §11).

    ``word_tables`` is the packed ``[3, V, K]`` int32 layout of
    `core/alias.py` (cut-bits / alias / W planes) built by
    ``mh.build_word_tables`` — the SAME builder, hence the same bits, as
    the trainer's traveling tables, so MH fold-in replays against the
    numpy mirrors exactly.  It is built lazily (:meth:`ensure_tables`)
    and exactly once: the ``scan`` sampler and perplexity scoring never
    need it, and rebuilding from counts is bit-deterministic, which is
    why :meth:`save` persists only the counts.

    The arrays fold-in reads are also kept on the device
    (:meth:`device_operands`): placed once, at the first serving use,
    and shared by every call until :meth:`release_device`.  The host
    views stay; the oracles and :meth:`save` read those, and the device
    copies hold the same bytes.
    """

    ckt: np.ndarray                       # [V, K] int32 word-topic counts
    ck: np.ndarray                        # [K] int32 topic totals
    alpha: np.ndarray                     # [K] f32 document prior
    beta: float                           # word smoothing
    word_tables: Optional[np.ndarray] = None   # packed [3, V, K] int32
    # set on row-restricted views (load_snapshot_rows): the smoothing
    # denominator must use the FULL vocabulary size, not the number of
    # resident rows, for sub-snapshot fold-in to stay bitwise
    true_vocab_size: Optional[int] = None
    _word_term: Optional[np.ndarray] = \
        dataclasses.field(default=None, repr=False, compare=False)
    _sparse_state: Optional[tuple] = \
        dataclasses.field(default=None, repr=False, compare=False)
    _fingerprint: Optional[str] = \
        dataclasses.field(default=None, repr=False, compare=False)
    # device copies by RESIDENT_OPERANDS name, and how often and how many
    # host bytes were placed into it
    _device: dict = dataclasses.field(default_factory=dict, init=False,
                                      repr=False, compare=False)
    placements: int = dataclasses.field(default=0, init=False, repr=False,
                                        compare=False)
    placed_bytes: int = dataclasses.field(default=0, init=False,
                                          repr=False, compare=False)

    @classmethod
    def from_counts(cls, ckt, ck=None, alpha=0.1, beta=0.01,
                    build_tables: bool = False) -> "ModelSnapshot":
        ckt = np.asarray(ckt, np.int32)
        if ckt.ndim != 2:
            raise ValueError(f"ckt must be [V, K], got shape {ckt.shape}")
        if ck is None:
            ck = ckt.sum(axis=0, dtype=np.int64)
        ck = np.asarray(ck, np.int32)
        k = ckt.shape[1]
        alpha = (np.full(k, alpha, np.float32) if np.isscalar(alpha)
                 else np.asarray(alpha, np.float32))
        snap = cls(ckt=ckt, ck=ck, alpha=alpha, beta=float(beta))
        if build_tables:
            snap.ensure_tables()
        return snap

    # -- shape views -------------------------------------------------------
    @property
    def vocab_size(self) -> int:
        return int(self.ckt.shape[0])

    @property
    def num_topics(self) -> int:
        return int(self.ckt.shape[1])

    @property
    def vbeta(self) -> float:
        v = (self.true_vocab_size if self.true_vocab_size is not None
             else self.vocab_size)
        return float(self.beta * v)

    # -- derived serving state --------------------------------------------
    def word_term(self) -> np.ndarray:
        """``φ̂ᵀ`` [V, K] f32: ``(C_k^t + β) / (C_k + Vβ)`` — row ``t`` is
        the per-topic probability of word ``t`` (rows of the transposed
        topic-word matrix; each COLUMN sums to 1 over the vocabulary).
        One f32 buffer shared by the device sampler and the host oracle,
        so the exact fold-in's conditionals agree bit-for-bit."""
        if self._word_term is None:
            denom = self.ck.astype(np.float32) + np.float32(self.vbeta)
            self._word_term = (self.ckt.astype(np.float32)
                               + np.float32(self.beta)) / denom[None, :]
        return self._word_term

    def sparse_state(self) -> tuple:
        """Frozen dense-segment layout for the ``sparse`` fold-in
        (DESIGN.md §12), built lazily and once per snapshot like the
        alias tables: ``(Xcs [V, K] f32, sX [V] f32)`` where ``X_v,k =
        φ̂ᵀ_v,k · α_k`` is the query-independent part of the fold-in
        conditional and ``Xcs`` its per-word cumsum.  Both the batched
        device fold-in and the serial host oracle consume this ONE
        buffer, so their dense-segment bisections agree bit-for-bit."""
        if self._sparse_state is None:
            xcs = np.cumsum(
                self.word_term() * self.alpha[None, :],
                axis=1, dtype=np.float32)
            self._sparse_state = (xcs, np.ascontiguousarray(xcs[:, -1]))
        return self._sparse_state

    def fingerprint(self) -> str:
        """Content identity of the frozen model (hex digest over counts +
        priors), computed lazily and once per snapshot.

        Two snapshots with the same fingerprint serve bitwise-identical
        responses — every derived quantity (``φ̂ᵀ``, alias tables, sparse
        state) is a deterministic function of exactly these bytes.  The
        serving scheduler (DESIGN.md §14) stamps it on every response
        alongside the swap epoch: the epoch says WHEN a model was
        installed, the fingerprint says WHAT was installed, so a swap to
        a bit-identical snapshot is observable as a new epoch with an
        unchanged fingerprint."""
        if self._fingerprint is None:
            import hashlib
            h = hashlib.sha256()
            h.update(np.asarray(
                [self.ckt.shape[0], self.ckt.shape[1],
                 self.true_vocab_size or 0], np.int64).tobytes())
            h.update(np.ascontiguousarray(self.ckt).tobytes())
            h.update(np.ascontiguousarray(self.ck).tobytes())
            h.update(np.ascontiguousarray(self.alpha).tobytes())
            h.update(np.float64(self.beta).tobytes())
            self._fingerprint = h.hexdigest()[:16]
        return self._fingerprint

    def ensure_tables(self) -> np.ndarray:
        """Build (once) and return the packed per-word alias tables,
        fetched from the device copy where one is placed."""
        if self.word_tables is None:
            tables = self._device.get("word_tables")
            if tables is None:
                from repro.core.mh import build_word_tables
                tables = build_word_tables(jnp.asarray(self.ckt),
                                           jnp.float32(self.beta))
            self.word_tables = np.asarray(tables)
        return self.word_tables

    # -- device residency --------------------------------------------------
    def _host_operand(self, name: str):
        if name in ("xcs", "sx"):
            xcs, sx = self.sparse_state()
            return sx if name == "sx" else xcs
        if name == "word_term":
            return self.word_term()
        if name in ("beta", "vbeta"):
            return np.float32(getattr(self, name))
        return getattr(self, name)

    def device_operands(self, sampler: str) -> tuple:
        """The device arrays ``sampler``'s fold-in sweeps take from the
        snapshot (``RESIDENT_OPERANDS``), placed on the first call that
        needs them and reused by every later one.

        A placement converts the host views under the ``foldin.place``
        span and adds one to ``placements`` and its host bytes to
        ``placed_bytes``; a host view that is already a device array is
        taken as it is and counts 0.  MH word tables not yet built are
        built on the device from the placed counts and kept there."""
        names = RESIDENT_OPERANDS[fold_in_family(sampler)]
        todo = [n for n in names if n not in self._device]
        if todo:
            host = {n: self._host_operand(n) for n in todo
                    if n != "word_tables" or self.word_tables is not None}
            nbytes = sum(np.asarray(x).nbytes for x in host.values()
                         if not isinstance(x, jax.Array))
            with tracing.span(tracing.FOLDIN_PLACE, bytes=nbytes):
                for n in todo:
                    if n in host:
                        self._device[n] = jnp.asarray(host[n])
                    else:
                        from repro.core.mh import build_word_tables
                        self._device[n] = build_word_tables(
                            self._device["ckt"], jnp.float32(self.beta))
            self.placements += 1
            self.placed_bytes += int(nbytes)
        return tuple(self._device[n] for n in names)

    def release_device(self) -> None:
        """Drop the device copies; a later fold-in places them again."""
        self._device.clear()

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> None:
        """Persist the counts (npz).  Tables are NOT stored: the builder
        is bit-deterministic, so a load + ``ensure_tables`` reproduces
        them exactly — the checkpoint stays sampler-agnostic, like the
        trainer's (DESIGN.md §10)."""
        import os

        from repro.data import integrity
        from repro.data.corpus import npz_stem
        stem = npz_stem(path)
        os.makedirs(os.path.dirname(stem) or ".", exist_ok=True)
        # atomic publish + crc32 sidecar (DESIGN.md §15): the serving
        # watcher and hot-swap validation key on this stamp
        integrity.save_npz(stem + ".npz", compressed=True,
                           ckt=self.ckt, ck=self.ck,
                           alpha=self.alpha, beta=np.float64(self.beta))


def load_snapshot(path: str) -> ModelSnapshot:
    from repro.data import integrity
    from repro.data.corpus import npz_stem
    data = integrity.load_npz(npz_stem(path) + ".npz")
    return ModelSnapshot.from_counts(data["ckt"], data["ck"],
                                     data["alpha"],
                                     float(data["beta"]))


# ---------------------------------------------------------------------------
# Sharded snapshot (out-of-core serving, DESIGN.md §13)
# ---------------------------------------------------------------------------

SHARDED_SNAPSHOT_FORMAT = "sharded-snapshot-v1"
SHARDED_SNAPSHOT_FORMAT_V2 = "sharded-snapshot-v2"
_SNAPSHOT_FORMATS = (SHARDED_SNAPSHOT_FORMAT, SHARDED_SNAPSHOT_FORMAT_V2)


def load_sharded_snapshot_meta(snap_dir: str) -> dict:
    """Manifest of a sharded snapshot directory
    (``StreamingLDA.save_snapshot_sharded`` output) — O(1) in model
    size.  Accepts v1 (plain dense ``.npy`` blocks, the pre-store
    layout) and v2 (blocks are CountStore records of the ``store`` kind
    stamped here); the returned dict always carries ``store``."""
    import json
    import os
    try:
        with open(os.path.join(snap_dir, "meta.json")) as f:
            meta = json.load(f)
    except OSError as e:
        raise ValueError(
            f"{snap_dir!r} is not a sharded snapshot directory "
            "(missing meta.json)") from e
    if meta.get("format") not in _SNAPSHOT_FORMATS:
        raise ValueError(
            f"unknown snapshot format {meta.get('format')!r} in "
            f"{snap_dir!r}; expected one of {_SNAPSHOT_FORMATS}")
    meta.setdefault("store", "dense")
    return meta


def load_snapshot_rows(snap_dir: str, word: np.ndarray):
    """Row-restricted snapshot view for one query batch: load ONLY the
    ``C_k^t`` rows of the batch's unique words (touching one block file
    per needed block), returning ``(snapshot, remapped_word_ids)`` for
    :func:`fold_in`.

    Every serving quantity is row-independent given the global ``C_k`` —
    ``φ̂ᵀ`` rows, sparse-state rows, and per-word alias tables are all
    computed per vocabulary row with the full-vocabulary smoothing
    denominator (``true_vocab_size`` keeps ``Vβ`` honest) — so fold-in
    against this view is BITWISE the full-snapshot fold-in, while peak
    serving memory is O(unique query words × K) + one block STORE at
    its occupancy — a TailStore block answers ``rows(idx)`` from its
    lanes + overflow dict (only the touched rows' heads and tails are
    ever densified), never ``[Vb, K]``, let alone ``[V, K]``.
    """
    import os

    from repro.core.engine import countstore
    from repro.data import integrity
    meta = load_sharded_snapshot_meta(snap_dir)
    word = np.asarray(word, np.int32)
    v, k = int(meta["vocab_size"]), int(meta["num_topics"])
    if word.size and (word.min() < 0 or word.max() >= v):
        raise ValueError(
            f"query word id outside [0, {v}) for snapshot {snap_dir!r}")
    uniq, inv = np.unique(word, return_inverse=True)
    uniq = uniq.astype(np.int64)
    vb = int(meta["block_size"])
    rows = np.zeros((max(uniq.shape[0], 1), k), np.int32)
    for b in np.unique(uniq // vb):
        sel = (uniq // vb) == b
        blk = countstore.load(
            os.path.join(snap_dir, f"block_{int(b):05d}"))
        rows[:uniq.shape[0]][sel] = blk.rows(uniq[sel] - b * vb)
    ck = integrity.load_npy(
        os.path.join(snap_dir, "ck.npy")).astype(np.int32)
    alpha = meta["alpha"]
    alpha = (np.full(k, alpha, np.float32) if np.isscalar(alpha)
             else np.asarray(alpha, np.float32))
    snap = ModelSnapshot(ckt=rows, ck=ck, alpha=alpha,
                         beta=float(meta["beta"]), true_vocab_size=v)
    return snap, inv.reshape(word.shape).astype(np.int32)


# ---------------------------------------------------------------------------
# Query batch layout
# ---------------------------------------------------------------------------

def pack_queries(docs: Sequence[Sequence[int]], t_pad: int | None = None,
                 q_pad: int | None = None):
    """Pack query docs (word-id sequences) into ``(word [Q, T] int32,
    mask [Q, T] bool)``.  ``t_pad``/``q_pad`` force bucket shapes (the
    serving path pads to power-of-two buckets so jit compiles once per
    bucket); padded slots are masked no-ops."""
    q = len(docs)
    lens = [len(d) for d in docs]
    t = int(t_pad) if t_pad is not None else max(lens + [1])
    t = max(t, 1)
    qq = int(q_pad) if q_pad is not None else max(q, 1)
    if qq < q:
        raise ValueError(f"q_pad {qq} < batch size {q}")
    if lens and max(lens) > t:
        raise ValueError(f"t_pad {t} < longest query ({max(lens)} tokens)")
    word = np.zeros((qq, t), np.int32)
    mask = np.zeros((qq, t), bool)
    for i, d in enumerate(docs):
        word[i, :lens[i]] = np.asarray(d, np.int32)
        mask[i, :lens[i]] = True
    return word, mask


def init_query_cdk(z0: np.ndarray, mask: np.ndarray, k: int) -> np.ndarray:
    """Initial per-query doc-topic counts from the initial assignments
    (shared by the engine and the host oracle)."""
    q, t = z0.shape
    cdk = np.zeros((q, k), np.int32)
    np.add.at(cdk, (np.repeat(np.arange(q), t), z0.reshape(-1)),
              mask.reshape(-1).astype(np.int32))
    return cdk


def theta_from_cdk(cdk: np.ndarray, alpha: np.ndarray) -> np.ndarray:
    """Posterior-mean mixture ``θ̂ = (C_d^k + α) / (N_d + Σα)`` [f64]."""
    cdk = np.asarray(cdk, np.float64)
    alpha = np.asarray(alpha, np.float64)
    return (cdk + alpha) / (cdk.sum(axis=1, keepdims=True) + alpha.sum())


# ---------------------------------------------------------------------------
# Device sweeps
# ---------------------------------------------------------------------------

@jax.jit
def fold_in_doc_scan(cdk_d, wterm, word_t, z_t, mask_t, u_t, alpha):
    """ONE query doc, ONE exact serial CGS sweep against the frozen word
    term.  This is the unit the engine vmaps over the batch — and the
    unit the host oracle replays serially (`kvstore.fold_in_oracle`), so
    batched and serial execution are the same jitted program applied
    per-row (the repo's standard bit-exactness argument)."""
    def body(carry, xs):
        cdk_d = carry
        t_i, k_old, valid, u_i = xs
        delta = valid.astype(jnp.int32)
        cdk_d = cdk_d.at[k_old].add(-delta)        # ¬dn self-exclusion
        p = wterm[t_i] * (alpha + cdk_d.astype(jnp.float32))
        k_new = sample_from_mass(p, u_i).astype(jnp.int32)
        k_new = jnp.where(valid, k_new, k_old)
        cdk_d = cdk_d.at[k_new].add(delta)
        return cdk_d, k_new

    return jax.lax.scan(body, cdk_d, (word_t, z_t, mask_t, u_t))


@jax.jit
def _fold_in_scan_sweeps(cdk, wterm, word, z, mask, u, alpha):
    """All sweeps × all query docs of the exact fold-in: `lax.scan` over
    the sweep axis of ``u`` [S, Q, T], vmap of :func:`fold_in_doc_scan`
    over the doc axis (docs are independent — the model is frozen)."""
    def sweep(carry, u_s):
        cdk, z = carry
        cdk, z = jax.vmap(fold_in_doc_scan,
                          in_axes=(0, None, 0, 0, 0, 0, None))(
            cdk, wterm, word, z, mask, u_s, alpha)
        return (cdk, z), None

    (cdk, z), _ = jax.lax.scan(sweep, (cdk, z), u)
    return cdk, z


@partial(jax.jit, static_argnames=("sampler_mode", "num_cycles"))
def _fold_in_mh_sweeps(cdk, ckt, ck, wtab, word, z, mask, u, alpha, beta,
                       vbeta, sampler_mode: str = "mh",
                       num_cycles: int = DEFAULT_MH_CYCLES):
    """MH fold-in: per sweep, build doc tables from sweep-start ``cdk``
    (the only mutable state) and run the registry's table-aware sampler
    per doc against the snapshot's STATIC word tables.  The model-count
    outputs of the sampler are discarded — that single difference from
    training is what "frozen model" means operationally."""
    from repro.core.engine.rounds import resolve_table_sampler
    sampler = resolve_table_sampler(sampler_mode)
    t = word.shape[1]
    zero_doc = jnp.zeros((t,), jnp.int32)

    def per_doc(cdk_d, dtab_d, w_t, z_t, m_t, u_t):
        out = sampler(cdk_d[None], ckt, ck, zero_doc, w_t, z_t, m_t, u_t,
                      alpha, beta, vbeta, wtab, dtab_d[:, None, :],
                      num_cycles=num_cycles)
        return out[0][0], out[3]          # cdk row + draws; ckt/ck frozen

    def sweep(carry, u_s):
        cdk, z = carry
        dtab = build_doc_tables(cdk, alpha)          # [3, Q, K] per sweep
        cdk, z = jax.vmap(per_doc, in_axes=(0, 1, 0, 0, 0, 0))(
            cdk, dtab, word, z, mask, u_s)
        return (cdk, z), None

    (cdk, z), _ = jax.lax.scan(sweep, (cdk, z), u)
    return cdk, z


@partial(jax.jit, static_argnames=("dcap",))
def fold_in_doc_sparse(cdk_d, wterm, xcs, sx, word_t, z_t, mask_t, u_t,
                       dcap: int):
    """ONE query doc, ONE hybrid sparse fold-in sweep (DESIGN.md §12).

    Frozen-count semantics per sweep, like the training sampler: the
    conditional ``p_k = φ̂ᵀ_t,k (α_k + C_d'^k)`` splits into the
    query-independent dense segment ``X = φ̂ᵀ·α`` (cumsummed once per
    snapshot) and the document-sparse lanes ``φ̂ᵀ·C_d'^k`` on the ≤ dcap
    nonzeros of the sweep-start doc row.  The model is frozen, so the
    rank-1 z0 exclusion lives entirely on the doc lanes (z0 is always a
    sweep-start nonzero) and the dense bisection needs no perturbation —
    simpler than training's head/tail machinery.  This per-doc unit is
    what the engine vmaps over the batch and the host oracle replays
    serially, the repo's standard bit-exactness argument."""
    from repro.core.sparse_device import (_extract_lanes, _lane_cumsum,
                                          _row_count, _segment_draw)
    k = cdk_d.shape[0]
    lanes = _extract_lanes(cdk_d[None], dcap)[0]           # [dcap]
    valid = lanes < k
    kk = jnp.minimum(lanes, k - 1)
    cdk_v = cdk_d.astype(jnp.float32)[kk]
    e = ((kk[None, :] == z_t[:, None])
         & mask_t[:, None]).astype(jnp.float32)
    wt_v = wterm[word_t[:, None], kk[None, :]]             # [T, dcap]
    dval = jnp.maximum(
        jnp.where(valid[None, :], wt_v * (cdk_v[None, :] - e), 0.0), 0.0)
    dcs = _lane_cumsum(dval)
    sd = dcs[:, -1]
    sxt = sx[word_t]
    total = sd + sxt                       # CDF order [doc lanes | dense]
    x = u_t * total
    in_d = x < sd
    kd = _segment_draw(dcs, sd, x,
                       jnp.broadcast_to(kk[None, :], dval.shape))
    y = x - sd
    idx = _row_count(xcs, word_t, y)
    last = _row_count(xcs, word_t, sxt, strict=True)
    k_dense = jnp.minimum(jnp.minimum(idx, last), k - 1).astype(jnp.int32)
    z_new = jnp.where(mask_t, jnp.where(in_d, kd, k_dense), z_t)
    d = mask_t.astype(jnp.int32)
    return cdk_d.at[z_t].add(-d).at[z_new].add(d), z_new


@partial(jax.jit, static_argnames=("dcap",))
def _fold_in_sparse_sweeps(cdk, wterm, xcs, sx, word, z, mask, u,
                           dcap: int):
    """All sweeps × all query docs of the sparse fold-in — the structure
    of ``_fold_in_scan_sweeps`` around :func:`fold_in_doc_sparse`."""
    unit = partial(fold_in_doc_sparse, dcap=dcap)

    def sweep(carry, u_s):
        cdk, z = carry
        cdk, z = jax.vmap(unit, in_axes=(0, None, None, None, 0, 0, 0, 0))(
            cdk, wterm, xcs, sx, word, z, mask, u_s)
        return (cdk, z), None

    (cdk, z), _ = jax.lax.scan(sweep, (cdk, z), u)
    return cdk, z


# ---------------------------------------------------------------------------
# Public fold-in entry point
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FoldInResult:
    cdk: np.ndarray      # [Q, K] int32 inferred doc-topic counts
    z: np.ndarray        # [Q, T] int32 final assignments (block layout)
    theta: np.ndarray    # [Q, K] f64 posterior-mean mixtures
    h2d_bytes: int = 0   # bytes of host arrays the call copied to the device


def fold_in(snapshot: ModelSnapshot, word: np.ndarray, mask: np.ndarray,
            num_sweeps: int = DEFAULT_FOLD_IN_SWEEPS, sampler: str = "scan",
            seed: int = 0, rng: Optional[np.random.Generator] = None,
            z0: Optional[np.ndarray] = None, u: Optional[np.ndarray] = None,
            num_cycles: int = DEFAULT_MH_CYCLES) -> FoldInResult:
    """Infer topic mixtures for a packed query batch (see
    :func:`pack_queries`) against a frozen snapshot.

    Randomness follows the trainer's convention: initial assignments
    ``z0`` [Q, T] and uniforms ``u`` [num_sweeps, Q, T] are drawn
    externally (from ``rng``/``seed`` unless supplied), so any run can be
    replayed draw-for-draw by `kvstore.fold_in_oracle` fed the same
    arrays.  ``sampler`` is ``"scan"`` (exact CGS) or any table-capable
    registry sampler (``"mh"``/``"mh_pallas"`` — the MH pair draws
    identically, as in training).

    The snapshot's operands come from its device copies
    (:meth:`ModelSnapshot.device_operands`), placed by the first call
    that needs them; the query's are converted here.  ``h2d_bytes``
    counts the host bytes the call converted: the query's, plus the
    snapshot's when this call placed them.  Arrays already on the
    device count 0.
    """
    word = np.asarray(word, np.int32)
    mask = np.asarray(mask, bool)
    if word.shape != mask.shape or word.ndim != 2:
        raise ValueError(f"word/mask must share a [Q, T] shape, got "
                         f"{word.shape} vs {mask.shape}")
    family = fold_in_family(sampler)
    k = snapshot.num_topics
    if rng is None:
        rng = np.random.default_rng(seed)
    if z0 is None:
        z0 = rng.integers(0, k, size=word.shape).astype(np.int32)
    if u is None:
        u = rng.random((num_sweeps, *word.shape), np.float32)
    u = np.asarray(u, np.float32)
    cdk0 = init_query_cdk(z0, mask, k)

    placed = snapshot.placed_bytes
    model = snapshot.device_operands(sampler)
    query = (cdk0, word, z0, mask, u)
    q_bytes = sum(np.asarray(x).nbytes for x in query
                  if not isinstance(x, jax.Array))
    with tracing.span(tracing.FOLDIN_UPLOAD, bytes=q_bytes):
        cdk0, word_d, z0, mask_d, u = [jnp.asarray(x) for x in query]
    with tracing.span(tracing.FOLDIN_RUN):
        if family == "scan":
            wterm, alpha = model
            cdk, z = _fold_in_scan_sweeps(cdk0, wterm, word_d, z0, mask_d,
                                          u, alpha)
        elif family == "sparse":
            # one jnp implementation serves both names: with the model
            # frozen there is no per-round lane extraction to fuse, so
            # the serving path has no separate kernel form (the alias
            # keeps `--sampler` choices symmetric between training and
            # inference).
            cdk, z = _fold_in_sparse_sweeps(cdk0, *model, word_d, z0,
                                            mask_d, u,
                                            dcap=min(k, word.shape[1]))
        else:
            ckt, ck, wtab, alpha, beta, vbeta = model
            cdk, z = _fold_in_mh_sweeps(cdk0, ckt, ck, wtab, word_d, z0,
                                        mask_d, u, alpha, beta, vbeta,
                                        sampler_mode=sampler,
                                        num_cycles=num_cycles)
    with tracing.span(tracing.FOLDIN_FETCH):
        cdk = np.asarray(cdk)
        z = np.asarray(z)
    with tracing.span(tracing.FOLDIN_THETA):
        theta = theta_from_cdk(cdk, snapshot.alpha)
    h2d = snapshot.placed_bytes - placed + q_bytes
    return FoldInResult(cdk=cdk, z=z, theta=theta, h2d_bytes=int(h2d))
