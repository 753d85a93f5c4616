"""The plain reference against brute force and against the program."""
import numpy as np

from reference import lda as ref


def test_mh_step_matches_the_transition_matrix():
    rng = np.random.default_rng(0)
    n, k = 6, 7
    p = rng.dirichlet(np.ones(k), size=n)
    pi = rng.random((n, k)) + 0.01
    q = rng.dirichlet(np.ones(k), size=n)
    pi[:, 2], q[:, 2] = pi[:, 3], q[:, 3]          # a tie in π/q
    got = ref.mh_step(p, pi, q)
    for r in range(n):
        mat = np.zeros((k, k))
        for i in range(k):
            for j in range(k):
                if i != j:
                    mat[i, j] = q[r, j] * min(
                        1.0, pi[r, j] * q[r, i] / (pi[r, i] * q[r, j]))
            mat[i, i] = 1.0 - mat[i].sum()
        np.testing.assert_allclose(got[r], p[r] @ mat, atol=1e-12)


def _small_state(seed=3, v=60, k=8, d=30):
    from harness import gen
    doc, word = gen.corpus(seed, d, v, {"doc_len_mean": 12,
                                         "doc_len_min": 2,
                                         "word_zipf_a": 1.1})
    z = np.random.default_rng(seed).integers(0, k, word.shape[0])
    return doc, word, z.astype(np.int32), v, k, d


def test_transition_matches_the_program_and_the_reference_sampler(jax):
    import jax.numpy as jnp

    from repro.core.engine import ModelParallelLDA
    from repro.data.corpus import Corpus
    doc, word, _, v, k, d = _small_state()
    lda = ModelParallelLDA(Corpus(doc, word, d, v), k, 1, alpha=0.1,
                           beta=0.01, seed=1, sampler_mode="mh",
                           track_error=False)
    lda.step()
    start = jax.tree.map(jnp.copy, lda.state)
    zb = lda.assignments()
    ckt, cdk, ck = ref.recount(word, doc, zb, v, d, k)
    p = ref.mh_transition(zb, ckt[word].astype(float),
                          cdk[doc].astype(float), ck, 0.1, 0.01, 0.01 * v,
                          2)
    rows = np.arange(word.shape[0])
    for name, draws in (("program", 300), ("reference", 200)):
        hits = np.zeros_like(p)
        for t in range(draws):
            if name == "program":
                lda.state = jax.tree.map(jnp.copy, start)
                lda._rng = np.random.default_rng(1000 + t)
                lda.step()
                za = lda.assignments()
            else:
                za = ref.mh_round(jax, jax.random.key(t), word, doc, zb, v,
                                  d, 0.1, 0.01, 2, jnp.float32, k)[0]
            hits[rows, za] += 1
        chi2 = ((hits - draws * p) ** 2 / (draws * p)).sum()
        dof = word.shape[0] * (k - 1)
        # chi-square with dof ~ 2,700: mean dof, sd sqrt(2 dof) ~ 74
        assert abs(chi2 - dof) < 6 * np.sqrt(2 * dof), (name, chi2, dof)


def test_foldin_outcomes_hold_a_float32_run():
    rng = np.random.default_rng(5)
    k = 50
    ckt = rng.integers(0, 3000, (40, k)) * (rng.random((40, k)) < 0.2)
    ck = ckt.sum(0) + 1
    alpha = np.full(k, 0.1, np.float32)
    for n in (4, 23, 48):
        toks = np.sort(rng.integers(0, 40, n))
        z0 = rng.integers(0, k, n)
        u = rng.random((5, n), dtype=np.float32)
        outs = ref.foldin_outcomes(
            ref.word_term(ckt[toks], ck, 0.01, 0.4), alpha, z0, u)
        for prec in ("f64", "f32"):
            th = ref.foldin_answers([ref.word_term(ckt[toks], ck, 0.01, 0.4,
                                                   prec)], alpha, [z0], [u],
                                    prec)[0]
            c = ref.counts_of_theta(th, n, alpha)
            assert c is not None and c.tobytes() in outs, (n, prec)


def test_bf16_round_rounds_to_nearest_even():
    x = np.asarray([1.0, 1.0 + 2 ** -8, 1.0 + 3 * 2 ** -8, 257.0, 1e-3],
                   np.float32)
    got = ref.bf16_round(x)
    assert got[0] == 1.0 and got[1] == 1.0          # tie to even
    assert got[2] == 1.0 + 2 ** -6                  # tie to even, upward
    assert got[3] == 256.0                          # 257 is not a bf16
    assert abs(got[4] - 1e-3) / 1e-3 < 2 ** -8
