"""Batched topic-inference serving facade (DESIGN.md §11).

The query-side counterpart of ``serve_step.BatchedServer``: accepts
variable-length query documents, packs them into padded power-of-two
buckets, and runs the fold-in engine (`core/infer.py`) against a frozen
:class:`~repro.core.infer.ModelSnapshot`.

Bucketing is the serving-side answer to XLA's static shapes: a batch of
``Q`` docs with longest length ``L`` is padded to ``(pow2(Q), pow2(L))``,
so the jitted fold-in compiles ONCE per bucket and every later batch
that lands in the same bucket reuses the executable.  Padded slots are
masked no-ops, proven not to perturb real queries bit-for-bit by
``tests/test_infer.py`` (pad invariance) — so bucket choice is purely a
latency/compile-cache knob, never a correctness one.

Queries never write model state, so servers scale horizontally with zero
coordination: run one process per replica and round-robin the traffic —
the embarrassing data-parallelism of frozen-model inference (§11).
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

from repro import tracing
from repro.core.infer import (DEFAULT_FOLD_IN_SWEEPS, ModelSnapshot,
                              fold_in, pack_queries)
from repro.core.likelihood import doc_completion_perplexity


def bucket_size(n: int, floor: int = 1) -> int:
    """Smallest power of two ≥ max(n, floor)."""
    b = max(int(floor), 1)
    while b < n:
        b <<= 1
    return b


class TopicInferenceServer:
    """Serve topic mixtures for unseen docs from a frozen snapshot.

    ``sampler`` is ``"scan"`` (exact CGS), the O(1) MH pair
    ``"mh"``/``"mh_pallas"``, or the hybrid ``"sparse"`` family
    (DESIGN.md §12) — per-snapshot derived state (packed word alias
    tables for MH, the dense-segment cumsum for sparse) is built once at
    server construction and shared by every query (the LightLDA
    frozen-model ideal).  Randomness flows from one seeded generator, so
    a server's response stream is reproducible end to end.
    """

    def __init__(self, snapshot: ModelSnapshot, sampler: str = "mh",
                 num_sweeps: int = DEFAULT_FOLD_IN_SWEEPS, seed: int = 0,
                 min_batch_bucket: int = 1, min_token_bucket: int = 8):
        self.snapshot = snapshot
        self.sampler = sampler
        self.num_sweeps = int(num_sweeps)
        self.min_batch_bucket = int(min_batch_bucket)
        self.min_token_bucket = int(min_token_bucket)
        self._rng = np.random.default_rng(seed)
        if sampler in ("sparse", "sparse_pallas"):
            snapshot.sparse_state()       # build once, serve many
        elif sampler != "scan":
            snapshot.ensure_tables()      # build once, serve many
        # serving observability: how many calls landed in each bucket
        # (tests assert reuse; ops would watch for bucket explosion)
        self.bucket_calls: Dict[Tuple[int, int], int] = {}
        self.docs_served = 0
        self.h2d_bytes = 0                # host -> device bytes, all calls

    def bucket_shape(self, docs: Sequence[Sequence[int]]
                     ) -> Tuple[int, int]:
        """(batch, token) bucket a set of docs pads into."""
        longest = max((len(d) for d in docs), default=1)
        return (bucket_size(len(docs), self.min_batch_bucket),
                bucket_size(longest, self.min_token_bucket))

    def infer(self, docs: Sequence[Sequence[int]]) -> np.ndarray:
        """Batched query: docs (word-id sequences) -> ``θ̂`` [len(docs), K].

        Pads to the power-of-two bucket, folds in, strips the padding.
        """
        if not len(docs):
            return np.zeros((0, self.snapshot.num_topics), np.float64)
        qb, tb = self.bucket_shape(docs)
        with tracing.span(tracing.FOLDIN_PACK):
            word, mask = pack_queries(docs, t_pad=tb, q_pad=qb)
        res = fold_in(self.snapshot, word, mask,
                      num_sweeps=self.num_sweeps, sampler=self.sampler,
                      rng=self._rng)
        self._count(qb, tb, len(docs), res)
        return res.theta[:len(docs)]

    def infer_with_draws(self, docs: Sequence[Sequence[int]],
                         z0_rows: Sequence[np.ndarray],
                         u_rows: Sequence[np.ndarray]) -> np.ndarray:
        """Batched query with EXTERNAL per-doc randomness — the serving
        scheduler's seed contract (DESIGN.md §14).

        Row ``i`` of the packed batch takes its initial assignments from
        ``z0_rows[i]`` ``[len_i]`` and its uniforms from ``u_rows[i]``
        ``[num_sweeps, len_i]``; pad slots are filled with inert zeros.
        Because every slot that can influence doc ``i`` is supplied by
        the caller, a doc's mixture is a pure function of (snapshot,
        tokens, its own draws) — independent of batch composition,
        bucket shape, and every other doc (the pad-invariance property,
        proven bitwise in tests/test_infer.py).  This is what lets the
        scheduler cache responses, compare them across swap epochs, and
        dispatch to any replica without changing a single bit.
        """
        if not len(docs):
            return np.zeros((0, self.snapshot.num_topics), np.float64)
        if len(z0_rows) != len(docs) or len(u_rows) != len(docs):
            raise ValueError(
                f"need one z0/u row per doc: {len(docs)} docs vs "
                f"{len(z0_rows)}/{len(u_rows)} rows")
        qb, tb = self.bucket_shape(docs)
        with tracing.span(tracing.FOLDIN_PACK):
            word, mask = pack_queries(docs, t_pad=tb, q_pad=qb)
            z0 = np.zeros((qb, tb), np.int32)
            u = np.zeros((self.num_sweeps, qb, tb), np.float32)
            for i, d in enumerate(docs):
                n = len(d)
                z_r = np.asarray(z0_rows[i], np.int32)
                u_r = np.asarray(u_rows[i], np.float32)
                if z_r.shape != (n,) or u_r.shape != (self.num_sweeps, n):
                    raise ValueError(
                        f"doc {i}: draws must be z0 [{n}] / u "
                        f"[{self.num_sweeps}, {n}], got {z_r.shape} / "
                        f"{u_r.shape}")
                z0[i, :n] = z_r
                u[:, i, :n] = u_r
        res = fold_in(self.snapshot, word, mask, num_sweeps=self.num_sweeps,
                      sampler=self.sampler, z0=z0, u=u)
        self._count(qb, tb, len(docs), res)
        return res.theta[:len(docs)]

    def _count(self, qb: int, tb: int, n_docs: int, res) -> None:
        self.bucket_calls[(qb, tb)] = self.bucket_calls.get((qb, tb), 0) + 1
        self.docs_served += n_docs
        self.h2d_bytes += res.h2d_bytes

    def infer_one(self, words: Sequence[int]) -> np.ndarray:
        """Single-doc convenience: word ids -> ``θ̂`` [K]."""
        return self.infer([words])[0]

    def perplexity(self, docs: Sequence[Sequence[int]]) -> dict:
        """Doc-completion perplexity of held-out docs under this server's
        snapshot and sampler (`core/likelihood.py`)."""
        return doc_completion_perplexity(
            self.snapshot, docs, num_sweeps=self.num_sweeps,
            sampler=self.sampler, rng=self._rng)
