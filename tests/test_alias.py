"""Alias-table construction and MH acceptance correctness.

Deterministic unit tests run everywhere; the hypothesis property tests
(Vose reconstruction over random sparse/dense/degenerate inputs) skip
when hypothesis is absent, mirroring ``test_properties.py``.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.alias import (SCALE, alias_cell_masses, alias_draw_int_np,
                              alias_draw_np, alias_int_cells_np,
                              alias_table_masses, build_alias_int,
                              build_alias_int_np, build_alias_int_rows,
                              build_alias_np, build_alias_tables,
                              int_masses_np, pack_tables, pack_tables_np,
                              split_cell_uniform, unpack_tables,
                              unpack_tables_np)
from repro.core.mh import (accept_ratio, build_doc_tables,
                           build_word_tables, sweep_block_mh,
                           sweep_block_mh_tables, uniform_streams,
                           uniform_streams_np)


# ---------------------------------------------------------------------------
# Classic float Vose construction — deterministic degenerate cases
# ---------------------------------------------------------------------------

DEGENERATE = [
    np.array([0.0, 0.0, 3.0, 0.0], np.float32),      # single nonzero
    np.ones(5, np.float32),                           # uniform
    np.zeros(4, np.float32),                          # zero mass
    np.array([1.0], np.float32),                      # K = 1
    np.array([1e-6, 1.0, 1e-6], np.float32),          # extreme skew
]


@pytest.mark.parametrize("p", DEGENERATE, ids=range(len(DEGENERATE)))
def test_vose_np_reconstructs_degenerate_inputs(p):
    prob, alias = build_alias_np(p.copy())
    assert prob.shape == p.shape and alias.shape == p.shape
    assert ((alias >= 0) & (alias < p.shape[0])).all()
    assert ((prob >= 0) & (prob <= 1 + 1e-6)).all()
    if p.sum() > 0:
        mass = alias_cell_masses(prob, alias, float(p.sum()))
        np.testing.assert_allclose(mass, p, rtol=3e-5,
                                   atol=3e-6 * max(p.sum(), 1))


def test_vose_np_draws_follow_distribution():
    p = np.array([1, 5, 0, 2, 8], np.float32)
    prob, alias = build_alias_np(p)
    rng = np.random.default_rng(0)
    u = rng.random(200_000).astype(np.float32)
    freq = np.bincount(alias_draw_np(prob, alias, u), minlength=5) / len(u)
    target = p / p.sum()
    assert np.abs(freq - target).max() < 0.01
    assert freq[2] == 0.0        # zero-mass topic is never drawn


# ---------------------------------------------------------------------------
# Integer-grid device construction (the production MH path)
# ---------------------------------------------------------------------------

INT_CASES = [
    (np.array([0, 0, 37, 0], np.int32), np.full(4, 0.01, np.float32)),
    (np.zeros(6, np.int32), np.full(6, 0.1, np.float32)),      # prior only
    (np.array([5], np.int32), np.array([0.3], np.float32)),    # K = 1
    (np.array([1000, 0, 1, 0, 999], np.int32),
     np.full(5, 0.01, np.float32)),                            # skew
    (np.arange(16, dtype=np.int32),
     np.linspace(0.01, 0.4, 16).astype(np.float32)),           # asym prior
]


@pytest.mark.parametrize("counts,prior", INT_CASES, ids=range(len(INT_CASES)))
def test_int_builder_jax_bit_equals_numpy_mirror(counts, prior):
    """The device builder and its numpy mirror compute the same closed
    form in exact integers — tables must agree BIT FOR BIT (the
    draw-for-draw replay of the MH backend rests on exactly this
    determinism)."""
    w = int_masses_np(counts, prior)
    cut_np, alias_np, u_np = build_alias_int_np(w)
    cut_j, alias_j, u_j = (np.asarray(x)
                           for x in build_alias_int(jnp.asarray(w)))
    np.testing.assert_array_equal(cut_j, cut_np)
    np.testing.assert_array_equal(alias_j, alias_np)
    assert float(u_j) == float(u_np)


@pytest.mark.parametrize("counts,prior", INT_CASES, ids=range(len(INT_CASES)))
def test_int_builder_reconstructs_masses(counts, prior):
    """Sum of cell masses equals the quantized input masses (·K units)
    exactly: every cut here is an integer below 2²⁴, so f32 holds it."""
    w = int_masses_np(counts, prior)
    cut, alias, u_cap = build_alias_int_np(w)
    k = w.shape[0]
    assert ((alias >= 0) & (alias < k)).all()
    assert (cut >= 0).all() and (cut <= u_cap).all()
    mass = alias_table_masses(cut, alias, u_cap)
    np.testing.assert_array_equal(mass, w.astype(np.float64) * k)


def _sweep_loop(w):
    """The textbook sequential sweep (Hübschle-Schneider & Sanders): lights
    in topic order each take their deficit from the current heavy; a heavy
    left with at most ``U`` closes (a cell short of ``U`` spills onto the
    next heavy, which may close in turn), and the last heavy stays open."""
    w = np.asarray(w, np.int64)
    k = w.shape[0]
    u = int(w.sum())
    m = w * k
    cut = np.full(k, u, np.int64)
    alias = np.arange(k)
    lights = [i for i in range(k) if m[i] < u]
    heavies = [j for j in range(k) if m[j] >= u]
    rest = m.copy()
    h = 0

    def close_spent():
        nonlocal h
        while h < len(heavies) - 1 and rest[heavies[h]] <= u:
            j, nxt = heavies[h], heavies[h + 1]
            if rest[j] < u:
                cut[j], alias[j] = rest[j], nxt
                rest[nxt] -= u - rest[j]
            h += 1

    close_spent()
    for i in lights:
        j = heavies[h]
        cut[i], alias[i] = m[i], j
        rest[j] -= u - m[i]
        close_spent()
    return cut, alias, u


def _edge_rows():
    k = 12
    one_heavy = np.ones(k, np.int32)
    one_heavy[5] = 400                     # all light but one
    alone = np.zeros(k, np.int32)
    alone[7] = 9                           # one topic holds everything
    rng = np.random.default_rng(4)
    rows = {"all_light_but_one": one_heavy,
            "all_equal": np.full(k, 5, np.int32),
            "one_topic_holds_all": alone,
            "k1": np.array([7], np.int32),
            "spill_cascade": np.array([1, 30, 1, 13, 13, 1, 1, 60, 1],
                                      np.int32)}
    for i in range(6):
        kk = int(rng.integers(2, 64))
        rows[f"random{i}"] = int_masses_np(
            rng.integers(0, 200, kk) * (rng.random(kk) < 0.4),
            np.full(kk, 0.05, np.float32))
    return rows


EDGE_ROWS = _edge_rows()


@pytest.mark.parametrize("name", sorted(EDGE_ROWS))
def test_int_cells_match_sequential_sweep(name):
    """The closed form pairs exactly as the sequential sweep does."""
    w = EDGE_ROWS[name]
    cut, alias, u = alias_int_cells_np(w)
    cut_l, alias_l, u_l = _sweep_loop(w)
    assert u == u_l
    np.testing.assert_array_equal(cut, cut_l)
    np.testing.assert_array_equal(alias, alias_l)


@pytest.mark.parametrize("name", sorted(EDGE_ROWS))
def test_int_cells_reconstruct_exact_masses(name):
    """Integer cells give back ``w·K`` exactly, every cut lies in
    ``[0, U]``, and the device builder equals the mirror bit for bit."""
    w = EDGE_ROWS[name]
    k = w.shape[0]
    cut, alias, u = alias_int_cells_np(w)
    assert ((cut >= 0) & (cut <= u)).all()
    np.testing.assert_array_equal(alias_table_masses(cut, alias, u),
                                  w.astype(np.float64) * k)
    cut_j, alias_j, u_j = (np.asarray(x)
                           for x in build_alias_int(jnp.asarray(w)))
    cut_np, alias_np, u_np = build_alias_int_np(w)
    np.testing.assert_array_equal(cut_j.view(np.int32),
                                  cut_np.view(np.int32))
    np.testing.assert_array_equal(alias_j, alias_np)
    assert float(u_j) == float(u_np) == float(u)


def test_overflow_row_is_exact_on_device_and_mirror():
    """A row whose ``K·U`` passes 2³¹ (one word with 1.5M tokens at
    K = 1,000, the size of this corpus's heaviest word): the two-word
    prefix sums keep the pairing exact, and device == mirror bitwise."""
    k = 1000
    counts = np.zeros(k, np.int32)
    counts[500] = 1_500_000
    counts[[3, 600, 999]] = [7, 40_000, 250_000]
    w = int_masses_np(counts, np.full(k, 0.01, np.float32))
    rows = np.stack([w, np.roll(w, 317)])
    cut, alias, u = alias_int_cells_np(w)
    assert k * u > 2 ** 31
    np.testing.assert_array_equal(alias_table_masses(cut, alias, u),
                                  w.astype(np.float64) * k)
    cut_j, alias_j, u_j = (np.asarray(x) for x in
                           build_alias_int_rows(jnp.asarray(rows)))
    for i, row in enumerate(rows):
        cut_np, alias_np, u_np = build_alias_int_np(row)
        np.testing.assert_array_equal(cut_j[i].view(np.int32),
                                      cut_np.view(np.int32))
        np.testing.assert_array_equal(alias_j[i], alias_np)
        assert float(u_j[i]) == float(u_np)
    np.testing.assert_array_equal(
        build_alias_int_np(w)[0], cut.astype(np.float32))


def test_int_builder_draws_follow_quantized_distribution():
    counts = np.array([3, 0, 11, 1, 25], np.int32)
    prior = np.full(5, 0.01, np.float32)
    w = int_masses_np(counts, prior)
    cut, alias, u_cap = build_alias_int_np(w)
    rng = np.random.default_rng(1)
    u = rng.random(200_000).astype(np.float32)
    d = alias_draw_int_np(cut, alias, float(u_cap), u)
    freq = np.bincount(d, minlength=5) / len(u)
    target = w / w.sum()
    assert np.abs(freq - target).max() < 0.01


def test_builder_refuses_k_past_two_word_range():
    """At K ≥ 2¹⁵ a word of the two-word prefix sums could overflow:
    the builder refuses the shape instead of building wrong tables."""
    with pytest.raises(ValueError, match="two-word"):
        build_alias_int_rows(jnp.ones((1, 1 << 15), jnp.int32))


def test_build_alias_tables_matches_per_row():
    rng = np.random.default_rng(2)
    counts = rng.integers(0, 40, (6, 17)).astype(np.int32)
    prior = (rng.random(17).astype(np.float32) + 0.01)
    cut, alias, u_cap, w = build_alias_tables(jnp.asarray(counts),
                                              jnp.asarray(prior))
    w_np = int_masses_np(counts, prior)
    np.testing.assert_array_equal(np.asarray(w), w_np)
    for i in range(counts.shape[0]):
        c_i, a_i, u_i = build_alias_int_np(w_np[i])
        np.testing.assert_array_equal(np.asarray(cut[i]), c_i)
        np.testing.assert_array_equal(np.asarray(alias[i]), a_i)
        assert float(u_cap[i]) == float(u_i)


def test_prior_quantization_keeps_full_support():
    """Every topic stays proposable even when the prior rounds to zero on
    the integer grid (the max(·, 1) floor — MH ergodicity needs it)."""
    prior = np.full(8, 1e-5, np.float32)        # << 1/SCALE
    w = int_masses_np(np.zeros(8, np.int32), prior)
    assert (w >= 1).all()
    cut, alias, u_cap = build_alias_int_np(w)
    d = alias_draw_int_np(cut, alias, float(u_cap),
                          np.linspace(0, 0.999, 4096).astype(np.float32))
    assert np.bincount(d, minlength=8).min() > 0
    assert SCALE * 0.01 >= 1    # the default β=0.01 grid is non-degenerate


def test_split_cell_uniform_in_range():
    u = jnp.asarray(np.array([0.0, 0.5, 0.999999, 1.0], np.float32))
    j, frac = split_cell_uniform(u, 7)
    assert ((np.asarray(j) >= 0) & (np.asarray(j) < 7)).all()
    assert (np.asarray(frac) >= 0).all()


# ---------------------------------------------------------------------------
# Shared uniform stream expansion (the replayability anchor)
# ---------------------------------------------------------------------------

def test_uniform_streams_numpy_mirror_is_bit_exact():
    rng = np.random.default_rng(2)
    u = rng.random(500).astype(np.float32)
    np.testing.assert_array_equal(
        uniform_streams_np(u, 8),
        np.asarray(uniform_streams(jnp.asarray(u), 8)))


def test_uniform_streams_are_uniform_and_decorrelated():
    rng = np.random.default_rng(3)
    u = rng.random(20_000).astype(np.float32)
    s = uniform_streams_np(u, 4)
    assert ((s >= 0) & (s < 1)).all()
    assert np.abs(s.mean(axis=1) - 0.5).max() < 0.01
    for i in range(4):
        for j in range(i + 1, 4):
            assert abs(np.corrcoef(s[i], s[j])[0, 1]) < 0.02


# ---------------------------------------------------------------------------
# MH acceptance — closed forms
# ---------------------------------------------------------------------------

def test_acceptance_is_one_when_proposal_equals_target():
    """q ∝ π  =>  A = [π(t) q(s)] / [π(s) q(t)] = 1 identically."""
    rng = np.random.default_rng(4)
    pi = rng.random(16).astype(np.float64) + 0.01
    q = 3.7 * pi                       # proportional proposal
    for s in range(16):
        for t in range(16):
            np.testing.assert_allclose(
                accept_ratio(pi[t], pi[s], q[t], q[s]), 1.0, rtol=1e-12)


def test_acceptance_two_topic_closed_form():
    """Hand-computed 2-topic case: the word-proposal acceptance for
    s=0 -> t=1 must equal

        A = [ (Cd1+a1)(Ct1+b)(C0+Vb) qw0 ] / [ (Cd0+a0)(Ct0+b)(C1+Vb) qw1 ]

    with qwk the (frozen, unexcluded) proposal mass and the ¬dn exclusion
    applied at the current topic s=0 in the target only.
    """
    a0, a1, b, vb = 0.1, 0.2, 0.01, 0.5
    cd = np.array([3.0, 1.0])     # doc-topic counts incl. current token @0
    ct = np.array([5.0, 7.0])     # word-topic counts incl. current token @0
    ck = np.array([40.0, 60.0])   # totals incl. current token @0
    # target with exclusion at topic 0 (the token's current assignment)
    pi0 = (cd[0] - 1 + a0) * (ct[0] - 1 + b) / (ck[0] - 1 + vb)
    pi1 = (cd[1] + a1) * (ct[1] + b) / (ck[1] + vb)
    q0, q1 = ct[0] + b, ct[1] + b
    expected = (pi1 * q0) / (pi0 * q1)
    by_hand = (((cd[1] + a1) * (ct[1] + b) * (ck[0] - 1 + vb) * (ct[0] + b))
               / ((cd[0] - 1 + a0) * (ct[0] - 1 + b) * (ck[1] + vb)
                  * (ct[1] + b)))
    np.testing.assert_allclose(accept_ratio(pi1, pi0, q1, q0), expected,
                               rtol=1e-12)
    np.testing.assert_allclose(expected, by_hand, rtol=1e-12)


def test_cross_multiplied_accept_matches_ratio_form():
    """The samplers decide ``u·π_s·q_t < π_t·q_s``; off fp-tie boundaries
    this is the same decision as ``u < accept_ratio``."""
    rng = np.random.default_rng(5)
    for _ in range(500):
        n_s, n_t, d_s, d_t, q_s, q_t = rng.random(6) + 0.05
        u = rng.random()
        ratio = accept_ratio(n_t / d_t, n_s / d_s, q_t, q_s)
        assert (u * n_s * d_t * q_t < n_t * d_s * q_s) == (u < ratio)


# ---------------------------------------------------------------------------
# MH block sweep — invariants and masking
# ---------------------------------------------------------------------------

def _block_state(rng, n=300, d=12, vb=20, k=8):
    doc = rng.integers(0, d, n).astype(np.int32)
    woff = np.sort(rng.integers(0, vb, n)).astype(np.int32)
    z = rng.integers(0, k, n).astype(np.int32)
    cdk = np.zeros((d, k), np.int32)
    ckt = np.zeros((vb, k), np.int32)
    np.add.at(cdk, (doc, z), 1)
    np.add.at(ckt, (woff, z), 1)
    return doc, woff, z, cdk, ckt, ckt.sum(0).astype(np.int32)


def test_mh_sweep_preserves_invariants():
    rng = np.random.default_rng(5)
    doc, woff, z, cdk, ckt, ck = _block_state(rng)
    n = doc.shape[0]
    u = rng.random(n).astype(np.float32)
    out = sweep_block_mh(
        jnp.asarray(cdk), jnp.asarray(ckt), jnp.asarray(ck),
        jnp.asarray(doc), jnp.asarray(woff), jnp.asarray(z),
        jnp.ones(n, bool), jnp.asarray(u), jnp.full(8, 0.1, jnp.float32),
        jnp.float32(0.01), jnp.float32(0.2))
    z_new = np.asarray(out[3])
    cdk2 = np.zeros_like(cdk); ckt2 = np.zeros_like(ckt)
    np.add.at(cdk2, (doc, z_new), 1)
    np.add.at(ckt2, (woff, z_new), 1)
    np.testing.assert_array_equal(np.asarray(out[0]), cdk2)
    np.testing.assert_array_equal(np.asarray(out[1]), ckt2)
    np.testing.assert_array_equal(np.asarray(out[2]), ckt2.sum(0))
    assert (z_new != z).any()          # the chain actually moves


def test_mh_sweep_masked_tokens_are_noops():
    rng = np.random.default_rng(6)
    doc, woff, z, cdk, ckt, ck = _block_state(rng, n=120)
    n = doc.shape[0]
    u = rng.random(n).astype(np.float32)
    out = sweep_block_mh(
        jnp.asarray(cdk), jnp.asarray(ckt), jnp.asarray(ck),
        jnp.asarray(doc), jnp.asarray(woff), jnp.asarray(z),
        jnp.zeros(n, bool), jnp.asarray(u), jnp.full(8, 0.1, jnp.float32),
        jnp.float32(0.01), jnp.float32(0.2))
    np.testing.assert_array_equal(np.asarray(out[0]), cdk)
    np.testing.assert_array_equal(np.asarray(out[1]), ckt)
    np.testing.assert_array_equal(np.asarray(out[3]), z)


def test_packed_table_roundtrip_bit_exact():
    """pack -> unpack is lossless for every plane, U is recomputed
    bit-identically from the W plane, and the numpy mirror agrees."""
    rng = np.random.default_rng(3)
    counts = rng.integers(0, 50, (6, 16)).astype(np.int32)
    prior = np.full((6, 16), 0.07, np.float32)
    cut, alias, u_cap, w = build_alias_tables(jnp.asarray(counts),
                                              jnp.asarray(prior))
    packed = pack_tables(cut, alias, w)
    assert packed.shape == (3, 6, 16) and packed.dtype == jnp.int32
    cut2, alias2, u2, w2 = unpack_tables(packed)
    np.testing.assert_array_equal(np.asarray(cut).view(np.int32),
                                  np.asarray(cut2).view(np.int32))
    np.testing.assert_array_equal(np.asarray(alias), np.asarray(alias2))
    np.testing.assert_array_equal(np.asarray(u_cap).view(np.int32),
                                  np.asarray(u2).view(np.int32))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w2))
    packed_np = pack_tables_np(np.asarray(cut), np.asarray(alias),
                               np.asarray(w))
    np.testing.assert_array_equal(np.asarray(packed), packed_np)
    for a, b in zip(unpack_tables_np(packed_np), (cut, alias, u_cap, w)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_tables_sweep_with_fresh_tables_equals_round_sweep():
    """Row independence of the Vose pairing: word/doc tables built
    separately (the per-iteration builders) are bit-identical to the
    slices of the concatenated per-round build, so feeding FRESH packed
    tables to ``sweep_block_mh_tables`` reproduces ``sweep_block_mh``
    exactly — the staleness of the iteration lifetime is purely a matter
    of WHEN the same builder ran."""
    rng = np.random.default_rng(8)
    doc, woff, z, cdk, ckt, ck = _block_state(rng, n=160, k=16)
    n = doc.shape[0]
    u = rng.random(n).astype(np.float32)
    alpha = jnp.full(16, 0.1, jnp.float32)
    args = (jnp.asarray(cdk), jnp.asarray(ckt), jnp.asarray(ck),
            jnp.asarray(doc), jnp.asarray(woff), jnp.asarray(z),
            jnp.ones(n, bool), jnp.asarray(u), alpha,
            jnp.float32(0.01), jnp.float32(0.2))
    wtab = build_word_tables(jnp.asarray(ckt), jnp.float32(0.01))
    dtab = build_doc_tables(jnp.asarray(cdk), alpha)
    out_round = sweep_block_mh(*args)
    out_tables = sweep_block_mh_tables(*args, wtab, dtab)
    for a, b in zip(out_round, out_tables):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mh_pallas_equals_mh():
    """The Pallas word-proposal kernel composes to the same draws as the
    pure-jnp MH sweep, bit for bit, given the same uniforms."""
    from repro.kernels.ops import sweep_block_mh_pallas
    rng = np.random.default_rng(7)
    doc, woff, z, cdk, ckt, ck = _block_state(rng, n=200, k=24)
    n = doc.shape[0]
    mask = rng.random(n) < 0.9
    u = rng.random(n).astype(np.float32)
    args = (jnp.asarray(cdk), jnp.asarray(ckt), jnp.asarray(ck),
            jnp.asarray(doc), jnp.asarray(woff), jnp.asarray(z),
            jnp.asarray(mask), jnp.asarray(u),
            jnp.full(24, 0.1, jnp.float32),
            jnp.float32(0.01), jnp.float32(0.2))
    out_m = sweep_block_mh(*args)
    out_p = sweep_block_mh_pallas(*args)
    for a, b in zip(out_m, out_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mh_cycle_kernel_word_grouped_layout():
    """Direct ``mh_cycle_call`` coverage of the word-grouped [G, Tg>1]
    layout the kernel is designed around (multi-token groups sharing one
    word's alias/count rows, a grid of several tiles), referenced
    against the jnp ``_mh_step`` cycle on the flattened tokens — the
    engine only exercises the degenerate Tg=1 form, so the [G, Tg, K]
    doc-row branches and [G, 1] capacity broadcasts are pinned here."""
    from repro.core.mh import _mh_step, block_proposal_tables
    from repro.kernels.mh_alias import mh_cycle_call

    rng = np.random.default_rng(11)
    k, vb, dloc, g, tg, tile_g = 24, 16, 12, 20, 4, 8
    n = g * tg
    gword = rng.integers(0, vb, g).astype(np.int32)    # one word per group
    woff = np.repeat(gword, tg)                        # flat [N]
    doc = rng.integers(0, dloc, n).astype(np.int32)
    z = rng.integers(0, k, n).astype(np.int32)
    cdk = np.zeros((dloc, k), np.int32)
    ckt = np.zeros((vb, k), np.int32)
    np.add.at(cdk, (doc, z), 1)
    np.add.at(ckt, (woff, z), 1)
    ck = ckt.sum(0).astype(np.int32)
    mask = (rng.random(n) < 0.9).astype(np.int32)
    u = rng.random(n).astype(np.float32)
    alpha = jnp.full(k, 0.1, jnp.float32)
    beta, vbeta = 0.01, 0.2

    word_table, doc_table = block_proposal_tables(
        jnp.asarray(cdk), jnp.asarray(ckt), alpha, beta)
    streams = uniform_streams(jnp.asarray(u), 8)        # 2 cycles

    # jnp reference on the flat token axis
    ckt_f = jnp.asarray(ckt, jnp.float32)
    cdk_f = jnp.asarray(cdk, jnp.float32)
    ck_f = jnp.asarray(ck, jnp.float32)
    z_ref = jnp.asarray(z)
    for c in range(2):
        z_ref = _mh_step(z_ref, jnp.asarray(z), jnp.asarray(doc),
                         jnp.asarray(woff), jnp.asarray(mask, bool),
                         streams[4 * c], streams[4 * c + 1],
                         jnp.asarray(woff), word_table,
                         cdk_f, ckt_f, ck_f, alpha, jnp.float32(beta),
                         jnp.float32(vbeta))
        z_ref = _mh_step(z_ref, jnp.asarray(z), jnp.asarray(doc),
                         jnp.asarray(woff), jnp.asarray(mask, bool),
                         streams[4 * c + 2], streams[4 * c + 3],
                         jnp.asarray(doc), doc_table,
                         cdk_f, ckt_f, ck_f, alpha, jnp.float32(beta),
                         jnp.float32(vbeta))

    # kernel operands in the grouped layout, padded to (tile_g, 128)
    wcut, walias, wu, wmass = (np.asarray(t) for t in word_table)
    dcut, dalias, du, dmass = (np.asarray(t) for t in doc_table)
    gp = -g % tile_g
    kp = -k % 128
    pad_g2 = lambda x: np.pad(x, ((0, gp), (0, kp)))
    pad_g3 = lambda x: np.pad(x.reshape(g, tg, -1),
                              ((0, gp), (0, 0), (0, kp)))
    pad_gt = lambda x: np.pad(x.reshape(g, tg), ((0, gp), (0, 0)))
    out = mh_cycle_call(
        jnp.asarray(pad_g2(wcut[gword])), jnp.asarray(pad_g2(walias[gword])),
        jnp.asarray(pad_g2(wmass[gword].astype(np.float32))),
        jnp.asarray(np.pad(wu[gword], (0, gp))[:, None]),
        jnp.asarray(pad_g3(dcut[doc])), jnp.asarray(pad_g3(dalias[doc])),
        jnp.asarray(pad_g3(dmass[doc].astype(np.float32))),
        jnp.asarray(pad_gt(du[doc])),
        jnp.asarray(pad_g2(np.asarray(ckt_f)[gword])),
        jnp.asarray(pad_g3(np.asarray(cdk_f)[doc])),
        jnp.asarray(pad_gt(z)),
        jnp.asarray(np.pad(np.asarray(streams).reshape(8, g, tg),
                           ((0, 0), (0, gp), (0, 0)))),
        jnp.asarray(pad_gt(mask)),
        jnp.asarray(np.pad(np.asarray(ck_f), (0, kp))),
        jnp.asarray(np.pad(np.asarray(alpha), (0, kp))),
        beta, vbeta, k_real=k, num_cycles=2, tile_g=tile_g,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(out)[:g].reshape(-1),
                                  np.asarray(z_ref))


def test_mh_pallas_tables_equals_mh_tables():
    """The fused Pallas cycle consumes external (possibly stale) packed
    tables bit-identically to the jnp table sweep — here with genuinely
    stale tables (built before perturbing the counts)."""
    from repro.kernels.ops import sweep_block_mh_pallas_tables
    rng = np.random.default_rng(9)
    doc, woff, z, cdk, ckt, ck = _block_state(rng, n=200, k=24)
    n = doc.shape[0]
    alpha = jnp.full(24, 0.1, jnp.float32)
    # stale tables: built from a DIFFERENT (earlier) count state
    z_old = rng.integers(0, 24, n).astype(np.int32)
    cdk_old = np.zeros_like(cdk); ckt_old = np.zeros_like(ckt)
    np.add.at(cdk_old, (doc, z_old), 1)
    np.add.at(ckt_old, (woff, z_old), 1)
    wtab = build_word_tables(jnp.asarray(ckt_old), jnp.float32(0.01))
    dtab = build_doc_tables(jnp.asarray(cdk_old), alpha)
    mask = rng.random(n) < 0.9
    u = rng.random(n).astype(np.float32)
    args = (jnp.asarray(cdk), jnp.asarray(ckt), jnp.asarray(ck),
            jnp.asarray(doc), jnp.asarray(woff), jnp.asarray(z),
            jnp.asarray(mask), jnp.asarray(u), alpha,
            jnp.float32(0.01), jnp.float32(0.2), wtab, dtab)
    out_m = sweep_block_mh_tables(*args)
    out_p = sweep_block_mh_pallas_tables(*args)
    for a, b in zip(out_m, out_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Hypothesis property tests (skipped when hypothesis is absent)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

    @pytest.mark.skip(reason="property tests need hypothesis")
    def test_property_tests_need_hypothesis():
        """Visible sentinel: the @given tests in this module were not
        collected because hypothesis is absent."""

if HAVE_HYPOTHESIS:
    @st.composite
    def _float_masses(draw):
        k = draw(st.integers(1, 64))
        kind = draw(st.sampled_from(["dense", "sparse", "single",
                                     "uniform"]))
        rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
        if kind == "dense":
            p = rng.random(k).astype(np.float32) * draw(
                st.floats(0.01, 100.0))
        elif kind == "sparse":
            p = rng.random(k).astype(np.float32)
            p[rng.random(k) < 0.8] = 0.0
        elif kind == "single":
            p = np.zeros(k, np.float32)
            p[rng.integers(0, k)] = draw(st.floats(0.001, 50.0))
        else:
            p = np.full(k, draw(st.floats(0.01, 10.0)), np.float32)
        return p

    @given(_float_masses())
    @settings(max_examples=60, deadline=None)
    def test_vose_np_reconstruction_property(p):
        """Cell masses sum back to p (fp tolerance); draws stay in range
        and never land on zero-mass topics."""
        prob, alias = build_alias_np(p.copy())
        assert ((alias >= 0) & (alias < p.shape[0])).all()
        if p.sum() > 0:
            mass = alias_cell_masses(prob, alias, float(p.sum()))
            np.testing.assert_allclose(
                mass, p, rtol=5e-5, atol=5e-6 * max(float(p.sum()), 1.0))
        rng = np.random.default_rng(0)
        d = alias_draw_np(prob, alias, rng.random(256).astype(np.float32))
        assert ((d >= 0) & (d < p.shape[0])).all()
        if p.sum() > 0:
            assert (p[d] > 0).all()

    @st.composite
    def _int_masses_case(draw):
        k = draw(st.integers(1, 64))
        kind = draw(st.sampled_from(["dense", "sparse", "single",
                                     "uniform"]))
        rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
        if kind == "dense":
            counts = rng.integers(0, 1000, k)
        elif kind == "sparse":
            counts = rng.integers(0, 100, k)
            counts[rng.random(k) < 0.8] = 0
        elif kind == "single":
            counts = np.zeros(k, np.int64)
            counts[rng.integers(0, k)] = draw(st.integers(1, 10_000))
        else:
            counts = np.full(k, draw(st.integers(0, 500)))
        prior = (rng.random(k).astype(np.float32)
                 * draw(st.floats(0.001, 2.0)))
        return counts.astype(np.int32), prior

    @given(_int_masses_case())
    @settings(max_examples=60, deadline=None)
    def test_int_builder_property(case):
        """Device builder == numpy mirror bitwise; the integer cells
        reconstruct the masses exactly and pair as the sequential sweep
        does; every draw index in range."""
        counts, prior = case
        w = int_masses_np(counts, prior)
        cut_np, alias_np, u_np = build_alias_int_np(w)
        cut_j, alias_j, u_j = (np.asarray(x)
                               for x in build_alias_int(jnp.asarray(w)))
        np.testing.assert_array_equal(cut_j, cut_np)
        np.testing.assert_array_equal(alias_j, alias_np)
        assert float(u_j) == float(u_np)
        k = w.shape[0]
        assert ((alias_np >= 0) & (alias_np < k)).all()
        cut_i, alias_i, u_i = alias_int_cells_np(w)
        np.testing.assert_array_equal(alias_table_masses(cut_i, alias_i, u_i),
                                      w.astype(np.float64) * k)
        cut_l, alias_l, _ = _sweep_loop(w)
        np.testing.assert_array_equal(cut_i, cut_l)
        np.testing.assert_array_equal(alias_i, alias_l)
        rng = np.random.default_rng(0)
        d = alias_draw_int_np(cut_np, alias_np, float(u_np),
                              rng.random(256).astype(np.float32))
        assert ((d >= 0) & (d < k)).all()
