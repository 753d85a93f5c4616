"""Vose alias tables — O(1) categorical draws for the MH sampler backend.

LightLDA (Yuan et al. 2014) makes the per-token cost of collapsed Gibbs
O(1) amortized by replacing the exact inverse-CDF draw over K topics with
a Metropolis–Hastings proposal drawn from an *alias table*: per-topic
arrays such that a single uniform yields an exact sample of the table's
distribution in two lookups (Walker 1977; Vose 1991).  Construction is
one row-wise merge (O(K log K) per row), done once per table lifetime
and amortized over every token that samples against the table — the
same build-once/consume-many shape as the paper's eq.-(3) word-major
cache.

**Determinism is load-bearing.**  The same table must be built bit-for-bit
by every compilation of the sampler — the vmap engine, the shard_map
engine, and the standalone host-oracle kernel — or MH replay stops being
draw-for-draw.  Plain f32 construction (sum → divide → compare against
1.0) is NOT stable across XLA programs: reductions and divisions lower
differently under different fusion, and a 1-ulp disagreement flips a
small/large classification into a different (still valid) table.  The
device builder therefore works on a fixed-point integer grid:

* masses are ``W_i = C_i·SCALE + max(round(prior_i·SCALE), 1)`` — pure
  int32 arithmetic (counts are ints; the prior is quantized once);
* the per-cell capacity is the INTEGER row total ``ΣW`` (masses are kept
  scaled by K, so no division ever happens);
* every decision of the build is an integer comparison, and the one fp
  value that feeds a draw decision (``frac·U < cut``) is a single IEEE
  multiply of integer-derived operands — nothing XLA can reassociate,
  recompute, or turn into a reciprocal.

Quantizing the prior perturbs only the *proposal*; the MH acceptance
(`core/mh.py`) evaluates the proposal mass from the same ``W`` grid and
the *target* from the unquantized counts, so the chain still targets the
exact eq.-(1) posterior (any proposal with full support is admissible).

Table encoding — row total ``U = f32(ΣW)``, per-cell ``cut``/``alias``:
cell ``j`` yields ``j`` when ``frac·U < cut[j]`` else ``alias[j]``, where
``frac`` is the within-cell uniform.  A full cell has ``cut = U`` and
``alias = j``.  The draw spends ONE uniform: the integer part of ``u·K``
picks the cell, the fractional part is the within-cell threshold (the
standard single-uniform alias trick).

**Construction: the sweep, in closed form.**  The pairing follows the
sweeping construction of Hübschle-Schneider & Sanders ("Parallel Weighted
Random Sampling", ESA 2019 / ACM TOMS 2022): lights (``m_i = K·W_i < U``)
in ascending topic order each take their deficit ``d_i = U − m_i`` from
the current heavy, heavies in ascending topic order; a heavy drawn below
``U`` becomes a cell of its own that spills onto the next heavy.  Which
heavy serves which light follows from prefix sums alone — ``Dex``/``Din``,
the exclusive/inclusive sums of the deficits over the row's lights, and
``Ein``, the inclusive sum of the surpluses ``e_j = m_j − U`` over its
heavies:

* a light gets ``cut = m_i`` and ``alias`` = the first heavy with
  ``Ein_j > Dex_i``;
* a heavy other than the row's last, with ``i*`` the last light whose
  ``Dex < Ein_j``, overshoots by ``o_j = Din_{i*} − Ein_j``; if ``o_j >
  0`` it gets ``cut = U − o_j`` and the next heavy as alias;
* every other cell is full.

:func:`build_alias_int_rows` finds both lookups with one row-wise sort
that merges the two monotone sequences — no loop over K and no scatter.

**Exact in integers.**  The prefix sums reach ``K·U``, past int32 for a
heavy word (about 4·10¹¹ for this repo's PubMed corpus), so the device
keeps them as two int32 words (``hi·2¹⁶ + lo``, each word summed on its
own) and compares them lexicographically; the overshoot, which lies in
``[0, U)``, is taken from a plain int32 sum that wraps mod 2³² and is
exact there.  Every decision is an integer comparison and ``cut`` is one
f32 convert of an int32 in ``[0, U]``, so the bits cannot depend on how
a program fuses or orders its sums — the reason the float prefix sum of
the textbook form is never used (DESIGN.md §9 rule 1).  The two sorts
feed no loop inside the builder; DESIGN.md §9 rule 2 is kept by the
cross-backend bitwise tests (vmap == shard_map == host replay), which
run the tables through the samplers' loops.

:func:`build_alias_int_np` mirrors the closed form in int64 numpy and is
asserted bit-equal by tests; :func:`build_alias_np` is the classic float
construction kept as the property-test reference for the pairing logic
itself.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# fixed-point grid for prior quantization: β/α enter proposal masses in
# units of 1/SCALE (target masses stay exact — see module docstring)
SCALE = 256


# ---------------------------------------------------------------------------
# Classic float Vose construction (numpy reference for property tests)
# ---------------------------------------------------------------------------

def build_alias_np(p: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Vose construction: ``p`` [K] nonnegative -> (prob [K] f32, alias [K]).

    Cell ``j`` holds mass ``prob[j]`` of topic ``j`` and ``1 - prob[j]`` of
    topic ``alias[j]`` (in units of ``sum(p)/K``); a zero-sum input yields
    the uniform table.
    """
    p = np.asarray(p, np.float32)
    k = p.shape[0]
    prob = np.ones(k, np.float32)
    alias = np.arange(k, dtype=np.int32)
    total = np.float32(p.sum(dtype=np.float64))
    if not total > 0:
        return prob, alias
    scaled = (p * (np.float32(k) / total)).astype(np.float32)
    small = [i for i in range(k) if scaled[i] < 1.0]
    large = [i for i in range(k) if scaled[i] >= 1.0]
    while small and large:
        s = small.pop()
        lg = large.pop()
        prob[s] = scaled[s]
        alias[s] = lg
        scaled[lg] = (scaled[lg] + scaled[s]) - np.float32(1.0)
        (small if scaled[lg] < 1.0 else large).append(lg)
    for i in small:          # fp residue: treat as full cells
        prob[i] = 1.0
    for i in large:
        prob[i] = 1.0
    return prob, alias


def alias_draw_np(prob: np.ndarray, alias: np.ndarray,
                  u: np.ndarray) -> np.ndarray:
    """Single-uniform draw from a :func:`build_alias_np` table."""
    k = prob.shape[0]
    x = np.asarray(u, np.float32) * np.float32(k)
    j = np.minimum(x.astype(np.int32), k - 1)
    frac = x - j.astype(np.float32)
    return np.where(frac < prob[j], j, alias[j]).astype(np.int32)


def alias_cell_masses(prob: np.ndarray, alias: np.ndarray,
                      total: float) -> np.ndarray:
    """Reconstruct the distribution a (prob, alias) table encodes: topic
    ``t`` receives ``prob[t]`` from its own cell plus ``1 - prob[j]`` from
    every cell aliased to it, in units of ``total / K``."""
    k = prob.shape[0]
    unit = np.float64(total) / k
    mass = prob.astype(np.float64) * unit
    np.add.at(mass, alias, (1.0 - prob.astype(np.float64)) * unit)
    return mass


# ---------------------------------------------------------------------------
# Fixed-point quantization shared by device builder and numpy mirror
# ---------------------------------------------------------------------------

def quantize_prior_np(prior: np.ndarray) -> np.ndarray:
    """Prior -> integer grid units: ``max(round(prior·SCALE), 1)``.

    The floor of 1 keeps every topic proposable (support ⊇ target), which
    MH needs for ergodicity; the acceptance uses these same quantized
    masses so no bias is introduced.
    """
    q = np.round(np.asarray(prior, np.float32) * np.float32(SCALE))
    return np.maximum(q, 1.0).astype(np.int32)


def _quantize_prior(prior: jax.Array) -> jax.Array:
    q = jnp.round(prior.astype(jnp.float32) * jnp.float32(SCALE))
    return jnp.maximum(q, 1.0).astype(jnp.int32)


def int_masses(counts: jax.Array, prior: jax.Array) -> jax.Array:
    """[..., K] int32 proposal masses ``W = C·SCALE + quantized prior``.

    Headroom: the binding constraint is the int32 ROW SUM ``ΣW`` (it
    becomes the table's cell capacity in :func:`build_alias_int_rows`),
    so a table row tolerates ``≈ 2³¹/SCALE ≈ 8.4M`` TOTAL tokens — a
    per-(worker, block) row count, bounded by one worker's share of one
    vocabulary block's postings (or one local doc's length), orders of
    magnitude below the limit at any geometry this engine runs.
    """
    return counts.astype(jnp.int32) * SCALE + _quantize_prior(prior)


def int_masses_np(counts: np.ndarray, prior: np.ndarray) -> np.ndarray:
    return (np.asarray(counts, np.int64) * SCALE
            + quantize_prior_np(prior)).astype(np.int32)


# ---------------------------------------------------------------------------
# Device (JAX) construction — the sweep in closed form, exact integers
# ---------------------------------------------------------------------------

_LO_BITS = 16                 # two-word integers: value = hi·2¹⁶ + lo
_LO_MASK = (1 << _LO_BITS) - 1


def _prefix2(hi: jax.Array, lo: jax.Array, exclusive: bool = False
             ) -> Tuple[jax.Array, jax.Array]:
    """Prefix sums along K of two-word values ``hi·2¹⁶ + lo`` (``lo`` in
    ``[0, 2¹⁶)``), normalized so the low word is again in ``[0, 2¹⁶)``.

    Each word is summed in int32 on its own: the low words stay under
    ``K·2¹⁶`` and the high words under the row's total over 2¹⁶, so
    neither overflows while ``K < 2¹⁵`` (checked by the builder)."""
    s_hi = jax.lax.cumsum(hi, axis=1)
    s_lo = jax.lax.cumsum(lo, axis=1)
    if exclusive:
        s_hi, s_lo = s_hi - hi, s_lo - lo
    return s_hi + (s_lo >> _LO_BITS), s_lo & _LO_MASK


@jax.jit
def build_alias_int_rows(w: jax.Array
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Alias tables from integer masses ``w`` [N, K] -> (cut, alias, U).

    The sweep construction of the module docstring, every row at once.
    With ``m_i = K·w_i`` and the integer capacity ``U = Σw``:

    * deficits ``d_i = U − m_i`` of the lights and surpluses ``e_j = m_j −
      U`` of the heavies get exact two-word prefix sums in topic order
      (``Dex`` exclusive over lights, ``Ein`` inclusive over heavies);
    * ONE row-wise ``lax.sort`` merges the two monotone sequences: a light
      keyed by ``Dex``, a heavy by ``Ein``, heavies first on a tie and
      topics in order, so a light's alias is the next heavy after it and
      a heavy is preceded by exactly the lights with ``Dex < Ein``;
    * in merged order one cumsum of ``v = m − U`` (``−d`` for a light,
      ``e`` for a heavy) gives each heavy's overshoot ``o = Din − Ein`` as
      its negation — the true value lies in ``[0, U)``, so the int32 sum,
      which wraps mod 2³², is exact there;
    * a reverse ``cummin`` names the next heavy, and a second sort by
      topic puts ``cut``/``alias`` back in topic order.

    No loop, no gather, no scatter: two sorts, prefix scans and
    elementwise ops along K.  ``cut`` is one f32 convert of an exact
    int32 in ``[0, U]``.
    """
    n, k = w.shape
    if k >= 1 << (31 - _LO_BITS):
        raise ValueError(f"K = {k} overflows the builder's two-word "
                         f"prefix sums (K < {1 << (31 - _LO_BITS)})")
    w = w.astype(jnp.int32)
    u = w.sum(axis=1)                                  # [N] exact int32
    uc = u[:, None]
    light = w <= (u - 1)[:, None] // k                 # ⇔ K·w < U
    d = jnp.where(light, uc - w * k, 0)                # exact: K·w < U
    # e = K·w − U of a heavy, in two words (K·w may pass 2³¹)
    e_lo = (w & _LO_MASK) * k - (uc & _LO_MASK)
    e_hi = (w >> _LO_BITS) * k - (uc >> _LO_BITS) + (e_lo >> _LO_BITS)
    e_hi = jnp.where(light, 0, e_hi)
    e_lo = jnp.where(light, 0, e_lo & _LO_MASK)
    dex_hi, dex_lo = _prefix2(d >> _LO_BITS, d & _LO_MASK, exclusive=True)
    ein_hi, ein_lo = _prefix2(e_hi, e_lo)
    topic = jax.lax.broadcasted_iota(jnp.int32, (n, k), 1)
    _, key_lo, s_topic, s_v = jax.lax.sort(
        (jnp.where(light, dex_hi, ein_hi),
         jnp.where(light, dex_lo * 2 + 1, ein_lo * 2),   # heavy first
         topic, w * k - uc),                              # v, mod 2³²
        dimension=1, num_keys=3)
    s_light = (key_lo & 1) == 1
    overshoot = -jax.lax.cumsum(s_v, axis=1)         # exact at heavies
    next_heavy = jax.lax.cummin(jnp.where(s_light, k, s_topic), axis=1,
                                reverse=True)
    after = jnp.concatenate(
        [next_heavy[:, 1:], jnp.full((n, 1), k, jnp.int32)], axis=1)
    spill = ~s_light & (overshoot > 0)
    s_cut = jnp.where(s_light, uc + s_v,
                      jnp.where(spill, uc - overshoot, uc))
    s_alias = jnp.where(s_light, next_heavy,
                        jnp.where(spill, after, s_topic))
    _, cut, alias = jax.lax.sort((s_topic, s_cut, s_alias), dimension=1,
                                 num_keys=1)
    return cut.astype(jnp.float32), alias, u.astype(jnp.float32)


def build_alias_int(w: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-row convenience form of :func:`build_alias_int_rows`."""
    cut, alias, u_cap = build_alias_int_rows(w[None, :])
    return cut[0], alias[0], u_cap[0]


@partial(jax.jit, static_argnames=())
def build_alias_tables(counts: jax.Array, prior: jax.Array
                       ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                  jax.Array]:
    """Counts [N, K] + prior ([K] or [N, K]) -> (cut, alias, U, W).

    ``W`` (the integer proposal masses) is returned alongside the table
    because the MH acceptance must evaluate the proposal density from the
    same quantized grid the table was built on.
    """
    prior = jnp.broadcast_to(prior, counts.shape)
    w = int_masses(counts, prior)
    cut, alias, u_cap = build_alias_int_rows(w)
    return cut, alias, u_cap, w


def alias_int_cells_np(w: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, int]:
    """The sweep's cells of one row in exact integers: ``w`` [K] ->
    (cut [K] int64 in ``[0, U]``, alias [K] int32, U).

    Numpy mirror of :func:`build_alias_int_rows`' closed form in int64
    (64-bit prefix sums, ``searchsorted`` for the merge)."""
    w = np.asarray(w, np.int32)
    k = w.shape[0]
    u = int(w.sum(dtype=np.int64).astype(np.int32))
    m = w.astype(np.int64) * k
    light = m < u
    d = np.where(light, u - m, 0)
    din = np.cumsum(d)
    dex = din - d
    ein = np.cumsum(np.where(light, 0, m - u))
    lights, heavies = np.flatnonzero(light), np.flatnonzero(~light)
    cut = np.full(k, u, np.int64)
    alias = np.arange(k, dtype=np.int32)
    cut[lights] = m[lights]
    first = np.searchsorted(ein[heavies], dex[lights], side="right")
    alias[lights] = heavies[np.minimum(first, len(heavies) - 1)]
    inner = heavies[:-1]                   # the last heavy is always full
    before = np.searchsorted(dex[lights], ein[inner], side="left")
    overshoot = np.r_[0, din[lights]][before] - ein[inner]
    spill = overshoot > 0
    cut[inner[spill]] = u - overshoot[spill]
    alias[inner[spill]] = heavies[1:][spill]
    return cut, alias, u


def build_alias_int_np(w: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Numpy mirror of :func:`build_alias_int` — tests assert the two
    agree bit for bit."""
    cut, alias, u = alias_int_cells_np(w)
    return (cut.astype(np.int32).astype(np.float32), alias,
            np.float32(np.int32(u)))


def alias_table_masses(cut: np.ndarray, alias: np.ndarray,
                       u_cap: float) -> np.ndarray:
    """Reconstruct the (·K-scaled) masses an integer-grid table encodes:
    topic ``t`` gets ``cut[t]`` from its own cell plus ``U - cut[j]`` from
    every cell aliased to it.  Equals ``w·K`` exactly for the integer
    cells of :func:`alias_int_cells_np`, and for f32 cells while every
    cut is below 2²⁴."""
    mass = cut.astype(np.float64).copy()
    np.add.at(mass, alias, np.float64(u_cap) - cut.astype(np.float64))
    return mass


# ---------------------------------------------------------------------------
# Packed (rotatable) table layout — the ring payload of traveling tables
# ---------------------------------------------------------------------------
#
# A built table is three [N, K] planes (cut f32, alias i32, W i32) plus the
# per-row capacity U [N] f32.  To let a table travel through the engine's
# rotation collective as ONE array (a single extra ppermute per round, and
# one slot queue to park it in), the planes are packed into a single int32
# array of shape [..., 3, N, K]:
#
#   plane 0 — cut,   IEEE-754 bits reinterpreted as int32 (lossless);
#   plane 1 — alias, already int32;
#   plane 2 — W,     the integer proposal masses.
#
# U is deliberately NOT packed: it is an exact int32 row sum of W
# (`build_alias_int_rows` computes it the same way), so the unpacker
# recomputes it bit-for-bit from plane 2 — one fewer plane to move and one
# fewer value whose staleness could diverge from the masses it summarizes.

def pack_tables(cut: jax.Array, alias: jax.Array,
                w: jax.Array) -> jax.Array:
    """(cut [.., N, K] f32, alias [.., N, K] i32, W [.., N, K] i32) ->
    packed int32 [.., 3, N, K] (bit-lossless; see layout note above)."""
    return jnp.stack([
        jax.lax.bitcast_convert_type(cut.astype(jnp.float32), jnp.int32),
        alias.astype(jnp.int32), w.astype(jnp.int32)], axis=-3)


def unpack_tables(packed: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Packed [.., 3, N, K] int32 -> (cut, alias, U, W) — the tuple shape
    every MH sweep consumes.  ``U`` is recomputed as the exact int32 row
    sum of the W plane, bit-identical to the value the builder produced."""
    cut = jax.lax.bitcast_convert_type(packed[..., 0, :, :], jnp.float32)
    alias = packed[..., 1, :, :]
    w = packed[..., 2, :, :]
    u_cap = w.sum(axis=-1).astype(jnp.float32)
    return cut, alias, u_cap, w


def pack_tables_np(cut: np.ndarray, alias: np.ndarray,
                   w: np.ndarray) -> np.ndarray:
    """Numpy mirror of :func:`pack_tables` (host-side tests/tools)."""
    return np.stack([np.asarray(cut, np.float32).view(np.int32),
                     np.asarray(alias, np.int32),
                     np.asarray(w, np.int32)], axis=-3)


def unpack_tables_np(packed: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
    """Numpy mirror of :func:`unpack_tables`."""
    packed = np.asarray(packed, np.int32)
    cut = packed[..., 0, :, :].view(np.float32)
    alias = packed[..., 1, :, :]
    w = packed[..., 2, :, :]
    u_cap = w.sum(axis=-1, dtype=np.int32).astype(np.float32)
    return cut, alias, u_cap, w


# ---------------------------------------------------------------------------
# Draw helpers (shared by jnp MH steps, Pallas kernel mirrors the math)
# ---------------------------------------------------------------------------

def split_cell_uniform(u: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """One uniform -> (cell index [int32], within-cell uniform [f32])."""
    x = u.astype(jnp.float32) * jnp.float32(k)
    j = jnp.minimum(x.astype(jnp.int32), k - 1)
    return j, x - j.astype(jnp.float32)


def alias_resolve(cut_cell: jax.Array, alias_cell: jax.Array,
                  u_cap: jax.Array, j: jax.Array,
                  frac: jax.Array) -> jax.Array:
    """Resolve a drawn cell: keep ``j`` iff ``frac·U < cut[j]`` (the
    division-free form of ``frac < cut[j]/U``)."""
    return jnp.where(frac * u_cap < cut_cell, j, alias_cell) \
        .astype(jnp.int32)


def alias_draw_int_np(cut: np.ndarray, alias: np.ndarray, u_cap: float,
                      u: np.ndarray) -> np.ndarray:
    """Numpy draw from an integer-grid table, vectorized over ``u``."""
    k = cut.shape[0]
    x = np.asarray(u, np.float32) * np.float32(k)
    j = np.minimum(x.astype(np.int32), k - 1)
    frac = x - j.astype(np.float32)
    return np.where(frac * np.float32(u_cap) < cut[j], j,
                    alias[j]).astype(np.int32)
