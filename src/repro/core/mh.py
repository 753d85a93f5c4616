"""O(1) alias-table Metropolis–Hastings sampler backend (LightLDA-style).

The exact samplers (``scan``/``batched``/``pallas``) pay O(K) per token:
an inverse-CDF draw must touch every topic lane.  LightLDA (Yuan et al.
2014) replaces the exact draw with a cycle of two Metropolis–Hastings
proposals that factor the eq.-(1) conditional through the word-major
buckets of SparseLDA (`core/sparse.py`):

  * **word proposal**  ``q_w(k) ∝ Ĉ_k^t + β``  — drawn from a Vose alias
    table built per *word row* of the resident block at round start;
  * **doc proposal**   ``q_d(k) ∝ Ĉ_d^k + α_k`` — drawn from an alias
    table built per *local document row* at round start.

Each proposal is corrected by the exact eq.-(1) acceptance ratio

    A(s -> t) = min(1, [π(t) q(s)] / [π(s) q(t)])

so the chain targets the same collapsed posterior as the exact samplers
even though the proposal tables are stale (built from round-start counts
Ĉ) and the proposal priors are quantized to the integer grid of
`core/alias.py` (the acceptance evaluates q from that same grid, so the
quantization shifts only the proposal, never the target).  Per-token
cost is O(1) amortized: the draw is two table lookups, the acceptance a
handful of scalar count gathers; the O((Vb + D_loc)·K) table build is
shared by every token that samples against it, and HOW LONG a table is
shared is the ``table_lifetime`` schedule (see below) — once per block
per round originally, once per iteration under traveling tables.

Determinism: every decision (cell pick, alias resolve, accept) compares
values produced by single IEEE ops on integer-derived operands — the
acceptance test is the division-free cross-multiplied form

    u·π(s)·q(t) < π(t)·q(s)   ⇔   u < A(s -> t)

(π = N/D expanded so only multiplications remain) — because f32
reductions and divisions do NOT lower bit-identically across the vmap /
shard_map / host-oracle compilations of this sampler, and draw-for-draw
replay (`kvstore`) plus cross-backend bit-identity demand that the SAME
uniforms always produce the SAME draws.

Staleness model (DESIGN.md §9): like ``batched``, this sampler freezes
the block-local counts at round start and applies the ¬dn self-exclusion
as a rank-1 correction at the token's round-start assignment; count
deltas fold in exactly at round end.  Draws are therefore
*distribution-equal* but not trajectory-equal to the exact chain —
validated statistically (`tests/test_mh_stats.py`) instead of bitwise.

Table lifetime (DESIGN.md §10): the acceptance ratio evaluates the
*target* from the live (round-start frozen) counts and the *proposal*
density from the table's own ``W`` grid, so ANY table with full support
keeps the chain exact — tables may be arbitrarily stale.  Two build
schedules exploit this:

* ``round`` — :func:`sweep_block_mh` builds word + doc tables from the
  round-start counts on every call (the original schedule, O((Vb +
  D_loc)·K) per block per round);
* ``iteration`` — the engine builds each block's word table once per
  iteration (at the block's first residency) and the doc tables once per
  iteration (from iteration-start ``cdk``), then feeds them to
  :func:`sweep_block_mh_tables` for every subsequent round.  Word tables
  travel the ring with their block in the packed ``core/alias.py``
  layout; the per-iteration build cost drops from ``B = S·M`` builds to
  ``S`` word builds + 1 doc build per worker.

Randomness: the engine supplies ONE external uniform per token per round.
:func:`uniform_streams` expands it into the ``4·num_cycles`` sub-draws a
token's MH cycle consumes via a splitmix32 hash of the uniform's IEEE
bits — pure integer arithmetic, mirrored bit-for-bit by
:func:`uniform_streams_np`, so a device MH run is replayable draw-for-draw
against the `kvstore` host oracle fed the same uniforms.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.alias import (alias_resolve, build_alias_tables,
                              pack_tables, split_cell_uniform,
                              unpack_tables)
from repro.core.sampler import fold_token_deltas

# MH proposal cycles per token per round (each cycle = one word proposal +
# one doc proposal, LightLDA's default depth).
DEFAULT_MH_CYCLES = 2

_GOLDEN = 0x9E3779B9          # stream-id spacing (Weyl constant)
_M1, _M2 = 0x21F0AAAD, 0x735A2D97  # splitmix32 finalizer multipliers


def _splitmix32(x):
    """splitmix32 finalizer on uint32 (jnp); wraps mod 2**32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(_M2)
    x = x ^ (x >> 15)
    return x


def uniform_streams(u: jax.Array, n: int) -> jax.Array:
    """Expand uniforms ``u`` [T] into ``n`` streams -> [n, T] f32.

    Stream ``i`` at token slot ``t`` hashes the IEEE-754 bits of ``u[t]``
    xored with ``(i+1)·GOLDEN`` and a token-lane salt ``t·M1``; uniforms
    are the top 24 bits scaled to [0, 1).  The lane salt matters: the
    engine's externally drawn f32 uniforms carry only 24 payload bits, so
    within a big block two tokens WILL collide — without the salt they
    would then share every proposal/accept sub-draw of the round.  The
    slot index is part of the shared (engine, host-oracle) token layout,
    so replayability is unaffected.
    """
    bits = jax.lax.bitcast_convert_type(u.astype(jnp.float32), jnp.uint32)
    lane = jnp.arange(u.shape[0], dtype=jnp.uint32) * jnp.uint32(_M1)
    ids = (jnp.arange(1, n + 1, dtype=jnp.uint32)
           * jnp.uint32(_GOLDEN))[:, None]
    h = _splitmix32((bits ^ lane)[None, :] ^ ids)
    return (h >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def uniform_streams_np(u: np.ndarray, n: int) -> np.ndarray:
    """Bit-exact numpy mirror of :func:`uniform_streams` (for tests)."""
    bits = np.asarray(u, np.float32).view(np.uint32)
    lane = (np.arange(bits.shape[0], dtype=np.uint32) * np.uint32(_M1))
    ids = (np.arange(1, n + 1, dtype=np.uint32)
           * np.uint32(_GOLDEN))[:, None]
    x = (bits ^ lane)[None, :] ^ ids
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(_M1)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(_M2)
    x = x ^ (x >> np.uint32(15))
    return (x >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)


# ---------------------------------------------------------------------------
# Acceptance ratio (pure, for unit tests / closed-form checks)
# ---------------------------------------------------------------------------

def accept_ratio(pi_new, pi_old, q_new, q_old):
    """MH acceptance ratio for proposal ``old -> new``:
    ``[π(new) q(old)] / [π(old) q(new)]``.  With ``q ∝ π`` this is
    identically 1 (the proposal IS the target).  The samplers decide
    ``u < ratio`` in the algebraically equivalent cross-multiplied form
    (see module docstring); this quotient form is the specification the
    unit tests pin down.
    """
    return (pi_new * q_old) / (pi_old * q_new)


def _target_terms(kk, d, t, z0, cdk_f, ckt_f, ck_f, alpha, beta, vbeta):
    """Numerator/denominator of the eq.-(1) mass at topic ``kk`` from
    frozen counts, with the ¬dn self-exclusion as a rank-1 correction at
    ``z0`` (the token's round-start assignment — its contribution sits in
    the frozen counts).  All args vectorized over tokens."""
    excl = (kk == z0).astype(jnp.float32)
    num = ((cdk_f[d, kk] - excl + alpha[kk])
           * (ckt_f[t, kk] - excl + beta))
    den = ck_f[kk] - excl + vbeta
    return num, den


def block_proposal_tables(cdk: jax.Array, ckt_block: jax.Array,
                          alpha: jax.Array, beta) -> Tuple[tuple, tuple]:
    """Round-start proposal state for one block: ONE concatenated table
    build over the word rows (prior β) and doc rows (prior α), so the
    row-wise merge runs once over ``Vb + D_loc`` rows.  Returns
    ``(word_table, doc_table)``, each ``(cut, alias, U, W)``.

    Shared by ``sweep_block_mh`` and the Pallas wrapper
    (`ops.sweep_block_mh_pallas`) — their bit-identity depends on this
    prologue staying common.
    """
    k = alpha.shape[0]
    vb = ckt_block.shape[0]
    prior = jnp.concatenate([
        jnp.broadcast_to(jnp.asarray(beta, jnp.float32), (vb, k)),
        jnp.broadcast_to(alpha, (cdk.shape[0], k))])
    cut, alias_t, u_cap, w = build_alias_tables(
        jnp.concatenate([ckt_block, cdk]), prior)
    word_table = (cut[:vb], alias_t[:vb], u_cap[:vb], w[:vb])
    doc_table = (cut[vb:], alias_t[vb:], u_cap[vb:], w[vb:])
    return word_table, doc_table


@jax.jit
def build_word_tables(ckt_block: jax.Array, beta) -> jax.Array:
    """One block's word-proposal tables (``q_w ∝ Ĉ_k^t + β``) in the
    packed rotatable layout: [Vb, K] counts -> [3, Vb, K] int32.

    Per-row bits are identical to the rows :func:`block_proposal_tables`
    builds — the sweep pairing is row-independent, so splitting the word
    rows out of the concatenated build changes nothing — which is what
    lets the per-iteration schedule coexist with the per-round one."""
    vb, k = ckt_block.shape
    prior = jnp.broadcast_to(jnp.asarray(beta, jnp.float32), (vb, k))
    cut, alias_t, _, w = build_alias_tables(ckt_block, prior)
    return pack_tables(cut, alias_t, w)


@jax.jit
def build_doc_tables(cdk: jax.Array, alpha: jax.Array) -> jax.Array:
    """One worker's doc-proposal tables (``q_d ∝ Ĉ_d^k + α_k``), packed:
    [D_loc, K] counts -> [3, D_loc, K] int32."""
    cut, alias_t, _, w = build_alias_tables(
        cdk, jnp.broadcast_to(alpha, cdk.shape))
    return pack_tables(cut, alias_t, w)


def _mh_step(z_cur, z0, d, t, mask, u_draw, u_acc, row, table,
             cdk_f, ckt_f, ck_f, alpha, beta, vbeta):
    """One MH proposal step, vectorized over the token axis.

    ``row`` selects the token's row of the proposal family's tables
    (``t`` for the word proposal, ``d`` for the doc proposal) and
    ``table = (cut, alias, U, W)`` is that family's alias table.  The
    target is always the eq.-(1) conditional; only the proposal differs.
    """
    cut, alias, u_cap, w = table
    k = ck_f.shape[0]
    j, frac = split_cell_uniform(u_draw, k)
    prop = alias_resolve(cut[row, j], alias[row, j], u_cap[row], j, frac)
    n_new, d_new = _target_terms(prop, d, t, z0, cdk_f, ckt_f, ck_f,
                                 alpha, beta, vbeta)
    n_old, d_old = _target_terms(z_cur, d, t, z0, cdk_f, ckt_f, ck_f,
                                 alpha, beta, vbeta)
    q_new = w[row, prop].astype(jnp.float32)
    q_old = w[row, z_cur].astype(jnp.float32)
    # u < [π_new q_old] / [π_old q_new], cross-multiplied (all factors > 0
    # for valid tokens); association order fixed left-to-right — the
    # Pallas kernel (`kernels/mh_alias.py`) mirrors this exact expression
    accept = u_acc * n_old * d_new * q_new < n_new * d_old * q_old
    return jnp.where(accept & mask, prop, z_cur)


# ---------------------------------------------------------------------------
# Numpy mirror of the MH cycle (host-oracle replay of frozen-count sweeps)
# ---------------------------------------------------------------------------

def _mh_step_np(z_cur, z0, d, t, mask, u_draw, u_acc, row, table,
                cdk_f, ckt_f, ck_f, alpha, beta, vbeta):
    """Numpy mirror of :func:`_mh_step`, op-for-op: same single-IEEE-op
    decision chains (cell pick, alias resolve, cross-multiplied accept),
    so given the same inputs it produces the same draws bit-for-bit —
    the fold-in host oracle (`kvstore.fold_in_oracle`) is built on it."""
    cut, alias_t, u_cap, w = table
    k = ck_f.shape[0]
    x = np.asarray(u_draw, np.float32) * np.float32(k)
    j = np.minimum(x.astype(np.int32), k - 1)
    frac = x - j.astype(np.float32)
    prop = np.where(frac * u_cap[row] < cut[row, j], j,
                    alias_t[row, j]).astype(np.int32)

    def target(kk):
        excl = (kk == z0).astype(np.float32)
        num = ((cdk_f[d, kk] - excl + alpha[kk])
               * (ckt_f[t, kk] - excl + beta))
        den = ck_f[kk] - excl + vbeta
        return num, den

    n_new, d_new = target(prop)
    n_old, d_old = target(z_cur)
    q_new = w[row, prop].astype(np.float32)
    q_old = w[row, z_cur].astype(np.float32)
    accept = u_acc * n_old * d_new * q_new < n_new * d_old * q_old
    return np.where(accept & mask, prop, z_cur).astype(np.int32)


def mh_cycle_np(z, doc, word_off, mask, u, cdk_f, ckt_f, ck_f, alpha,
                beta, vbeta, word_table, doc_table,
                num_cycles: int = DEFAULT_MH_CYCLES) -> np.ndarray:
    """Numpy mirror of the ``_mh_sweep_core`` z-update: run the full MH
    cycle against FROZEN f32 count views and the given alias tables
    (each ``(cut, alias, U, W)`` numpy tuples, e.g. from
    ``alias.unpack_tables_np``).  Returns the new assignments; the caller
    owns the count-delta fold, which is what lets the fold-in oracle
    reuse this with the model counts simply never folded."""
    streams = uniform_streams_np(np.asarray(u, np.float32), 4 * num_cycles)
    z0 = np.asarray(z, np.int32)
    z_cur = z0.copy()
    mask = np.asarray(mask, bool)
    beta = np.float32(beta)
    vbeta = np.float32(vbeta)
    alpha = np.asarray(alpha, np.float32)
    for c in range(num_cycles):
        z_cur = _mh_step_np(z_cur, z0, doc, word_off, mask,
                            streams[4 * c], streams[4 * c + 1], word_off,
                            word_table, cdk_f, ckt_f, ck_f, alpha, beta,
                            vbeta)
        z_cur = _mh_step_np(z_cur, z0, doc, word_off, mask,
                            streams[4 * c + 2], streams[4 * c + 3], doc,
                            doc_table, cdk_f, ckt_f, ck_f, alpha, beta,
                            vbeta)
    return np.where(mask, z_cur, z0).astype(np.int32)


# ---------------------------------------------------------------------------
# Engine-facing block samplers
# ---------------------------------------------------------------------------

def _mh_sweep_core(cdk, ckt_block, ck, doc, word_off, z, mask, u,
                   alpha, beta, vbeta, word_table, doc_table, num_cycles):
    """Shared sweep body: run the MH cycles against the given proposal
    tables (fresh or stale — the acceptance corrects either) and fold the
    count deltas exactly.  The target terms always come from the live
    round-start counts passed in, never from the tables."""
    ckt_f = ckt_block.astype(jnp.float32)
    cdk_f = cdk.astype(jnp.float32)
    ck_f = ck.astype(jnp.float32)
    streams = uniform_streams(u, 4 * num_cycles)

    z_cur = z
    for c in range(num_cycles):
        z_cur = _mh_step(
            z_cur, z, doc, word_off, mask, streams[4 * c],
            streams[4 * c + 1], word_off, word_table,
            cdk_f, ckt_f, ck_f, alpha, beta, vbeta)
        z_cur = _mh_step(
            z_cur, z, doc, word_off, mask, streams[4 * c + 2],
            streams[4 * c + 3], doc, doc_table,
            cdk_f, ckt_f, ck_f, alpha, beta, vbeta)

    z_new = jnp.where(mask, z_cur, z)
    return fold_token_deltas(cdk, ckt_block, ck, doc, word_off, z, z_new,
                             mask)


@partial(jax.jit, static_argnames=("num_cycles",))
def sweep_block_mh(cdk: jax.Array, ckt_block: jax.Array, ck: jax.Array,
                   doc: jax.Array, word_off: jax.Array, z: jax.Array,
                   mask: jax.Array, u: jax.Array,
                   alpha: jax.Array, beta: jax.Array, vbeta: jax.Array,
                   num_cycles: int = DEFAULT_MH_CYCLES
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Alias-table MH sweep over one block; registry signature/semantics
    of ``sweep_block_batched`` (frozen per round, deltas folded exactly).
    Round table lifetime: builds fresh word + doc tables on every call.

    Per round: O((Vb + D_loc)·K) to build the word/doc alias tables, then
    O(num_cycles) per token — table lookups and scalar count gathers only,
    never a [T, K] mass materialization.
    """
    word_table, doc_table = block_proposal_tables(cdk, ckt_block, alpha,
                                                  beta)
    return _mh_sweep_core(cdk, ckt_block, ck, doc, word_off, z, mask, u,
                          alpha, beta, vbeta, word_table, doc_table,
                          num_cycles)


@partial(jax.jit, static_argnames=("num_cycles",))
def sweep_block_mh_tables(cdk: jax.Array, ckt_block: jax.Array,
                          ck: jax.Array, doc: jax.Array,
                          word_off: jax.Array, z: jax.Array,
                          mask: jax.Array, u: jax.Array,
                          alpha: jax.Array, beta: jax.Array,
                          vbeta: jax.Array, word_packed: jax.Array,
                          doc_packed: jax.Array,
                          num_cycles: int = DEFAULT_MH_CYCLES
                          ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
    """Iteration table lifetime: MH sweep against CALLER-OWNED packed
    proposal tables (``word_packed`` [3, Vb, K] built at the block's first
    residency and rotated with it, ``doc_packed`` [3, D_loc, K] built from
    iteration-start ``cdk``) — zero table-build cost on this path.

    The tables may be up to ``B - 1`` rounds stale; the eq.-(1) acceptance
    evaluates q from the tables' own ``W`` grid and the target from the
    live round-start counts, so the chain's invariant distribution is the
    same as :func:`sweep_block_mh`'s (DESIGN.md §10).
    """
    return _mh_sweep_core(cdk, ckt_block, ck, doc, word_off, z, mask, u,
                          alpha, beta, vbeta, unpack_tables(word_packed),
                          unpack_tables(doc_packed), num_cycles)
