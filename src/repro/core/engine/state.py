"""Engine state: per-worker slot queues + construction and gathering.

State layout (DESIGN.md §3, §8).  With ``M`` model workers and ``S``
blocks per worker the vocabulary is split into ``B = S·M`` blocks; each
worker keeps a length-``S`` FIFO of ``[Vb, K]`` word-topic blocks.  Slot 0
is the *resident* block — the only one touched by compute and the only one
that travels in the per-round rotation; slots ``1..S-1`` are *parked*
(they model the paper's distributed key-value store / host offload, where
non-resident blocks live outside worker RAM).

Nothing in this layout is sampler-specific: the alias tables of the
``mh`` backend (DESIGN.md §9) are derived state — built inside the
sampler at round start under ``table_lifetime="round"``, or built and
rotated by the backends as iteration-local payloads under the
traveling-table schedule (DESIGN.md §10, where every table a round
reads was built earlier in the SAME iteration) — so the pytree carries
no table arrays and checkpoints are sampler-agnostic either way.

Hybrid data×model parallelism (DESIGN.md §8) adds ``D`` data replicas:
every per-worker array keeps ONE leading axis of length ``R = D·M``
(row ``g = d·M + m``, data-major), so at ``D = 1`` shapes are bit-for-bit
those of the original 1D engine.  Documents are sharded ``R`` ways; the
block queues are REPLICATED along data (replica ``d``'s row ``d·M + m``
holds the same blocks as row ``m``) and reconciled by a per-round delta
psum on the data axis.
"""
from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import schedule as sched
from repro.core.counts import CountState
from repro.core.invindex import (InvertedIndex, build_inverted_index,
                                 common_block_capacity, scatter_assignments)
from repro.data.corpus import Corpus
from repro.data.sharding import WorkerShard, grid_shard


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MPState:
    """Stacked per-worker state (leading axis = the ``R = D·M`` grid rows,
    data-major; ``R == M`` when ``data_parallel == 1``)."""

    cdk: jax.Array        # [R, Dloc, K]
    ckt: jax.Array        # [R, S, Vb, K] slot queue; slot 0 = resident
    block_id: jax.Array   # [R, S] which block sits in each slot
    ck_synced: jax.Array  # [K] totals agreed at last round boundary
    ck_local: jax.Array   # [R, K] per-worker drifting view (§3.3)
    z: jax.Array          # [R, B, T] assignments in inverted-index layout

    def tree_flatten(self):
        return ((self.cdk, self.ckt, self.block_id, self.ck_synced,
                 self.ck_local, self.z), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- shape views -------------------------------------------------------
    @property
    def num_shards(self) -> int:
        """Grid rows ``R = D·M`` (== ``M`` for the 1D engine)."""
        return self.ckt.shape[0]

    @property
    def num_workers(self) -> int:
        return self.ckt.shape[0]

    @property
    def blocks_per_worker(self) -> int:
        return self.ckt.shape[1]

    @property
    def resident_ckt(self) -> jax.Array:
        """[R, Vb, K] — the block each worker is actively sampling."""
        return self.ckt[:, 0]

    @property
    def resident_block(self) -> jax.Array:
        """[R] — id of each worker's resident block."""
        return self.block_id[:, 0]

    def local_ck_views(self) -> np.ndarray:
        return np.asarray(self.ck_local)

    def true_ck(self) -> np.ndarray:
        return np.asarray(self.ck_synced) + (
            np.asarray(self.ck_local)
            - np.asarray(self.ck_synced)[None, :]).sum(axis=0)


@dataclasses.dataclass
class EngineLayout:
    """Static (non-pytree) engine geometry: shards, indexes, partition.

    Built once per ``(corpus, M, S)``; everything here is host-side numpy
    plus the token-layout arrays shared by every round, which
    :func:`build_layout` leaves in host memory and :func:`place_layout`
    puts on the device(s).
    """

    corpus: Corpus
    num_workers: int
    blocks_per_worker: int
    data_parallel: int
    partition: sched.VocabPartition
    shards: List[WorkerShard]
    indexes: List[InvertedIndex]
    capacity: int
    doc: jax.Array    # [R, B, T] int32
    woff: jax.Array   # [R, B, T] int32
    mask: jax.Array   # [R, B, T] bool

    @property
    def num_slots(self) -> int:
        """Token slots one iteration samples, padding included:
        ``B·R·T`` (every round, every grid row, its whole group)."""
        return self.num_blocks * self.num_shards * self.capacity

    @property
    def num_blocks(self) -> int:
        return self.partition.num_blocks

    @property
    def num_shards(self) -> int:
        """Worker-grid rows ``R = D·M`` — leading axis of every array."""
        return self.data_parallel * self.num_workers

    @property
    def num_rounds(self) -> int:
        """Rounds per iteration — every (worker, block) pair meets once."""
        return self.num_blocks

    @property
    def resident_block_rows(self) -> int:
        """Rows of the resident ``ckt`` block: ``ceil(V / (S·M))``."""
        return self.partition.block_size


def build_layout(corpus: Corpus, num_workers: int,
                 blocks_per_worker: int = 1,
                 data_parallel: int = 1) -> EngineLayout:
    """Shard documents ``R = D·M`` ways, partition the vocabulary into
    ``B = S·M`` blocks (shared across data replicas), and build each grid
    cell's per-block inverted index with a common capacity.  The token
    arrays stay in host memory until :func:`place_layout`."""
    num_blocks = num_workers * blocks_per_worker
    partition = sched.partition_vocab(corpus.vocab_size, num_blocks)
    sched.validate_schedule_2d(data_parallel, num_workers, blocks_per_worker)
    shards = [grid_shard(corpus, d, m, data_parallel, num_workers)
              for d in range(data_parallel) for m in range(num_workers)]
    cap = common_block_capacity((s.word for s in shards), partition)
    indexes = [build_inverted_index(s.doc_local, s.word, partition, cap)
               for s in shards]
    doc = np.stack([i.doc for i in indexes])
    woff = np.stack([i.word_off for i in indexes])
    mask = np.stack([i.mask for i in indexes])
    return EngineLayout(
        corpus=corpus, num_workers=num_workers,
        blocks_per_worker=blocks_per_worker, data_parallel=data_parallel,
        partition=partition,
        shards=shards, indexes=indexes, capacity=cap,
        doc=doc, woff=woff, mask=mask)


def init_state(layout: EngineLayout, num_topics: int,
               z0: np.ndarray) -> MPState:
    """Build the initial :class:`MPState` from token-order assignments.

    Slot-major placement: block ``b = s·M + m`` starts in slot ``s`` of
    worker ``m`` (``schedule.home_slot``), so at ``S = 1`` worker ``m``
    opens holding block ``m`` exactly as the original engine did.  With
    ``D > 1`` data replicas the block queues of the ``M`` model positions
    are tiled along data: grid row ``d·M + m`` opens with the same queue
    as row ``m`` (replicated model, DESIGN.md §8).  The arrays stay in
    host memory until :func:`place_state`.
    """
    m, s_ = layout.num_workers, layout.blocks_per_worker
    d_, r_ = layout.data_parallel, layout.num_shards
    b, k = layout.num_blocks, num_topics
    part, cap = layout.partition, layout.capacity
    vb = part.block_size
    dloc = layout.shards[0].num_local_docs

    cdk = np.zeros((r_, dloc, k), np.int32)
    ckt_blocks = np.zeros((b, vb, k), np.int32)
    zarr = np.zeros((r_, b, cap), np.int32)
    for g, (shard, idx) in enumerate(zip(layout.shards, layout.indexes)):
        zz = z0[shard.token_id]
        np.add.at(cdk[g], (shard.doc_local, zz), 1)
        blk = part.block_of_word(shard.word)
        off = part.word_offset_in_block(shard.word)
        np.add.at(ckt_blocks, (blk, off, zz), 1)
        real = idx.mask
        zarr[g][real] = zz[idx.token_id[real]]
    ck = ckt_blocks.sum(axis=(0, 1)).astype(np.int32)

    # [B, Vb, K] -> [M, S, Vb, K]: block s·M + m into (worker m, slot s);
    # then tile the queues along the data axis -> [R = D·M, S, Vb, K]
    slots = ckt_blocks.reshape(s_, m, vb, k).swapaxes(0, 1)
    slots = np.broadcast_to(slots[None], (d_, m, s_, vb, k)) \
        .reshape(r_, s_, vb, k)
    block_id = (np.arange(s_)[None, :] * m
                + np.arange(m)[:, None]).astype(np.int32)
    block_id = np.broadcast_to(block_id[None], (d_, m, s_)) \
        .reshape(r_, s_)
    return MPState(
        cdk=cdk,
        ckt=np.ascontiguousarray(slots),
        block_id=np.ascontiguousarray(block_id),
        ck_synced=ck,
        ck_local=np.broadcast_to(ck, (r_, k)),
        z=zarr,
    )


# ---------------------------------------------------------------------------
# Placement
# ---------------------------------------------------------------------------
# Every per-row array has the grid rows ``R = D·M`` as its leading axis.
# On one device (the vmap backend) the arrays are plain device arrays.  On
# a mesh (shard_map) each is split by ``rows``, the partition the
# iteration's ``in_specs`` give it, so every device receives only its own
# rows, straight from host memory; ``ck_synced`` is whole on each.

def put(x, rows: NamedSharding | None = None) -> jax.Array:
    """``x`` on the default device (``rows=None``) or split over the mesh
    by ``rows``."""
    return jnp.asarray(x) if rows is None else jax.device_put(x, rows)


def _whole(rows: NamedSharding | None) -> NamedSharding | None:
    return None if rows is None else NamedSharding(rows.mesh, P())


def place_layout(layout: EngineLayout,
                 rows: NamedSharding | None = None) -> EngineLayout:
    """The layout with its token arrays placed (see :func:`put`)."""
    return dataclasses.replace(layout, doc=put(layout.doc, rows),
                               woff=put(layout.woff, rows),
                               mask=put(layout.mask, rows))


def place_state(state: MPState,
                rows: NamedSharding | None = None) -> MPState:
    """The state placed (see :func:`put`): every per-row array split by
    ``rows``, the agreed totals ``ck_synced`` whole on every device."""
    return MPState(cdk=put(state.cdk, rows), ckt=put(state.ckt, rows),
                   block_id=put(state.block_id, rows),
                   ck_synced=put(state.ck_synced, _whole(rows)),
                   ck_local=put(state.ck_local, rows),
                   z=put(state.z, rows))


def gather_counts(layout: EngineLayout, state: MPState,
                  num_topics: int) -> CountState:
    """Reassemble the global model (the KV-store "dump").

    Only replica 0's queues are read for ``C_k^t``: at iteration (and
    round) boundaries every replica's copy of a block is identical — the
    per-round delta psum reconciles them — so any replica is the model.
    """
    s_ = layout.blocks_per_worker
    vb = layout.partition.block_size
    v, k = layout.corpus.vocab_size, num_topics
    ckt_full = np.zeros((layout.num_blocks * vb, k), np.int32)
    blocks = np.asarray(state.block_id)
    ckt = np.asarray(state.ckt)
    for w in range(layout.num_workers):       # replica 0 rows: g = m
        for s in range(s_):
            blk = int(blocks[w, s])
            ckt_full[blk * vb:(blk + 1) * vb] = ckt[w, s]
    ckt_full = ckt_full[:v]
    cdk_full = np.zeros((layout.corpus.num_docs, k), np.int32)
    cdk = np.asarray(state.cdk)
    for w, shard in enumerate(layout.shards):
        real = shard.doc_global >= 0
        cdk_full[shard.doc_global[real]] = cdk[w][:real.sum()]
    ck = ckt_full.sum(axis=0).astype(np.int32)
    return CountState(jnp.asarray(cdk_full), jnp.asarray(ckt_full),
                      jnp.asarray(ck))


def gather_assignments(layout: EngineLayout, state: MPState) -> np.ndarray:
    """Current z in original token order."""
    z = np.zeros(layout.corpus.num_tokens, np.int32)
    zs = np.asarray(state.z)
    for w, (shard, idx) in enumerate(zip(layout.shards, layout.indexes)):
        z_local = scatter_assignments(idx, zs[w], shard.token_id.shape[0])
        z[shard.token_id] = z_local
    return z


# ---------------------------------------------------------------------------
# CountStore bridging (DESIGN.md §16)
# ---------------------------------------------------------------------------
# The device chain keeps MPState.ckt dense — jit/donation/ppermute need
# static shapes — so the CountStore boundary for the in-memory engine is
# AT REST: these helpers encode/decode the [R, S, Vb, K] slot queue as a
# flat list of per-slot store records for checkpoints (and any future
# host-side parking of non-resident slots).

def ckt_to_stores(ckt: np.ndarray, kind: str, wcap: int) -> list:
    """Encode every ``(r, s)`` slot of the queue as a CountStore of
    ``kind`` (exact integer round-trip)."""
    from repro.core.engine import countstore
    r, s, vb, k = ckt.shape
    cls = countstore.resolve_store(kind)
    return [cls.from_dense(ckt[i, j], wcap=wcap)
            for i in range(r) for j in range(s)]


def ckt_from_stores(stores: list, r: int, s: int) -> np.ndarray:
    """Inverse of :func:`ckt_to_stores`: rebuild the dense slot queue."""
    if len(stores) != r * s:
        raise ValueError(
            f"expected {r * s} store records, got {len(stores)}")
    vb, k = stores[0].shape
    out = np.zeros((r, s, vb, k), np.int32)
    for i in range(r):
        for j in range(s):
            out[i, j] = stores[i * s + j].to_dense()
    return out
