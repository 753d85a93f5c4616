"""Plain collapsed-Gibbs LDA: counts, the Metropolis-Hastings transition a
training iteration must draw from, and fold-in replay for serving.

Training (LightLDA's cycle, as the configuration states it).  Within one
round every token samples against the counts frozen at the round's start,
with its own assignment ``s`` excluded from the target

    π(k) ∝ (C_dk − [k=s] + α_k)(C_wk − [k=s] + β) / (C_k − [k=s] + Vβ),

by ``cycles`` pairs of MH steps: a word step with proposal
``q_w(k) ∝ SCALE·C_wk + ⌈β⌉`` and a doc step with ``q_d(k) ∝ SCALE·C_dk +
⌈α_k⌉``, where ``⌈x⌉ = max(round(x·SCALE), 1)`` is the prior on the
proposal grid, both built from the round-start counts with ``s``
included.  :func:`mh_transition` gives the exact distribution of a
token's topic after the round, for the statistics in
``checks/mh_transition.py``.

Serving (fold-in against a frozen model, the ``sparse`` draw rule).  A
query's tokens start at ``z0`` and take ``sweeps`` sweeps; in each, every
token draws against the sweep-start doc counts from
``p_k = φ_wk (α_k + C_dk − [k=z])`` by one uniform ``u`` and the inverse
CDF of the masses in this order: first ``φ_wk (C_dk − [k=z])`` over the
topics with ``C_dk > 0``, then ``φ_wk α_k`` over all topics, each
ascending, with ``φ_wk = (C_wk + β)/(C_k + Vβ)``.  The answer is
``θ = (C_d + α)/(n + Σα)``.  :func:`foldin_outcomes` follows every draw
in float64 and, where the uniform lies within float32 rounding of a
boundary, both sides of it."""
from __future__ import annotations

import hashlib

import numpy as np

SCALE = 256                   # the proposal grid: priors in 1/SCALE units
F32_EPS = 2.0 ** -24          # unit roundoff of float32
# rounding spread of a float32 prefix sum of n positive terms, in units
# of 2ε times the sum: "any_order" bounds every summation order (a chain
# of n terms rounds n − 1 times); "typical" is the size that rounding
# errors of independent sign reach in practice, for a first, cheap pass
TYPICAL_SPREAD = 3.0
SPREAD = {"any_order": lambda n: n + 4.0,
          "typical": lambda n: TYPICAL_SPREAD * np.sqrt(n) + 4.0}


# ---------------------------------------------------------------------------
# Counts
# ---------------------------------------------------------------------------

def recount(word, doc, z, vocab_size: int, num_docs: int, num_topics: int):
    """``(ckt [V, K], cdk [D, K], ck [K])`` int64, counted from the
    assignments."""
    k = num_topics
    z = np.asarray(z, np.int64)
    ckt = np.bincount(np.asarray(word, np.int64) * k + z,
                      minlength=vocab_size * k).reshape(vocab_size, k)
    cdk = np.bincount(np.asarray(doc, np.int64) * k + z,
                      minlength=num_docs * k).reshape(num_docs, k)
    return ckt, cdk, ckt.sum(axis=0)


def grid_prior(x) -> np.ndarray:
    return np.maximum(np.round(np.asarray(x, np.float64) * SCALE), 1.0)


# ---------------------------------------------------------------------------
# Training: the exact MH transition of one round
# ---------------------------------------------------------------------------

def _rowwise_counts(key_sorted, key, side):
    """Per row, ``#{i : key_sorted[r, i] <= key[r, j]}`` (``side='right'``)
    or ``<`` (``'left'``), by one flat search over row-offset keys."""
    n, k = key.shape
    off = (np.arange(n, dtype=np.float64) * 1e4)[:, None]
    flat = (key_sorted + off).ravel()
    pos = np.searchsorted(flat, (key + off).ravel(), side=side)
    return pos.reshape(n, k) - (np.arange(n) * k)[:, None]


def mh_step(p, pi, q):
    """One MH step, rows = tokens: from topic distribution ``p`` with
    proposal ``q`` (rows sum to 1) and target ``pi`` (unnormalised).

    With ``R = π/q`` the move ``i → j`` has probability
    ``q_j min(1, R_j/R_i)``, so after sorting by ``R``

        p'_j = q_j (A_j − p_j) + p_j (1 − B_j + q_j),
        A_j = Σ_{R_i ≤ R_j} p_i + R_j Σ_{R_i > R_j} p_i / R_i,
        B_j = Σ_{R_l ≥ R_j} q_l + Σ_{R_l < R_j} q_l R_l / R_j.
    """
    n, k = p.shape
    logr = np.log(pi) - np.log(q)
    order = np.argsort(logr, axis=1, kind="stable")
    ls = np.take_along_axis(logr, order, axis=1)
    rs = np.exp(ls)
    ps = np.take_along_axis(p, order, axis=1)
    qs = np.take_along_axis(q, order, axis=1)
    zero = np.zeros((n, 1))
    p_le = np.concatenate([zero, np.cumsum(ps, axis=1)], axis=1)
    p_over_r_gt = np.concatenate(
        [np.cumsum((ps / rs)[:, ::-1], axis=1)[:, ::-1], zero], axis=1)
    q_ge = np.concatenate(
        [np.cumsum(qs[:, ::-1], axis=1)[:, ::-1], zero], axis=1)
    qr_lt = np.concatenate([zero, np.cumsum(qs * rs, axis=1)], axis=1)
    hi = _rowwise_counts(ls, logr, "right")
    lo = _rowwise_counts(ls, logr, "left")
    r = np.exp(logr)
    a = (np.take_along_axis(p_le, hi, axis=1)
         + r * np.take_along_axis(p_over_r_gt, hi, axis=1))
    b = (np.take_along_axis(q_ge, lo, axis=1)
         + np.take_along_axis(qr_lt, lo, axis=1) / r)
    return q * (a - p) + p * (1.0 - b + q)


def mh_transition(s, ckt_rows, cdk_rows, ck, alpha, beta, vbeta,
                  cycles: int):
    """Exact topic distribution ``[n, K]`` after one round for tokens with
    round-start topic ``s`` [n] and round-start count rows of their word
    (``ckt_rows`` [n, K]) and doc (``cdk_rows`` [n, K])."""
    n, k = ckt_rows.shape
    alpha = np.broadcast_to(np.asarray(alpha, np.float64), (k,))
    e = np.zeros((n, k))
    e[np.arange(n), s] = 1.0
    pi = ((cdk_rows - e + alpha) * (ckt_rows - e + beta)
          / (np.asarray(ck, np.float64)[None, :] - e + vbeta))
    qw = SCALE * ckt_rows + grid_prior(beta)
    qd = SCALE * cdk_rows + grid_prior(alpha)[None, :]
    qw = qw / qw.sum(axis=1, keepdims=True)
    qd = qd / qd.sum(axis=1, keepdims=True)
    p = e.copy()
    for _ in range(cycles):
        p = mh_step(p, pi, qw)
        p = mh_step(p, pi, qd)
    return p


def transition_scores(p, s, z_new):
    """``(stay_z, logp_z)``: standard scores of the number of tokens that
    kept their topic, and of the summed log-probability of the drawn
    topics, under the exact transition ``p`` [n, K]."""
    rows = np.arange(p.shape[0])
    p = np.maximum(p, 1e-300)
    e_stay = p[rows, s]
    stay = (np.asarray(z_new) == np.asarray(s)).sum()
    stay_z = (stay - e_stay.sum()) / np.sqrt((e_stay * (1 - e_stay)).sum())
    logp = np.log(p)
    mean = (p * logp).sum(axis=1)
    var = (p * logp ** 2).sum(axis=1) - mean ** 2
    logp_z = ((logp[rows, z_new].sum() - mean.sum())
              / np.sqrt(var.sum()))
    return float(stay_z), float(logp_z)


def mh_round(jax, key, word, doc, z, vocab_size, num_docs, alpha, beta,
             cycles: int, dtype, num_topics: int, count_dtype=None):
    """The reference sampler: one round of the cycle above on the device,
    with arithmetic in ``dtype`` and counts held in ``count_dtype``
    (``dtype`` unless given).  Proposals are drawn without
    tables: the word step picks a uniformly chosen occurrence of the word
    and takes its round-start topic with probability
    ``SCALE·n_w / (SCALE·n_w + K⌈β⌉)``, else a uniform topic, which is
    ``q_w`` exactly (the doc step likewise).  Returns ``(z_new, ckt, cdk,
    ck)`` with the counts as held."""
    import jax.numpy as jnp
    k = num_topics
    count_dtype = count_dtype or dtype
    n = word.shape[0]
    alpha = jnp.broadcast_to(jnp.asarray(alpha, dtype), (k,))
    qb = float(grid_prior(beta))
    qa = jnp.asarray(grid_prior(np.broadcast_to(
        np.asarray(alpha, np.float64), (k,))), dtype)
    by_word = np.argsort(word, kind="stable").astype(np.int32)
    w_count = np.bincount(word, minlength=vocab_size)
    w_start = (np.cumsum(w_count) - w_count).astype(np.int32)
    d_count = np.bincount(doc, minlength=num_docs)
    d_start = (np.cumsum(d_count) - d_count).astype(np.int32)
    if np.any(np.diff(doc) < 0):
        raise ValueError("the reference sampler needs doc-sorted tokens")

    @jax.jit
    def run(key, word, doc, z, by_word, w_count, w_start, d_count,
            d_start):
        one = jnp.ones((n,), count_dtype)
        ckt = jnp.zeros((vocab_size, k), count_dtype).at[word, z].add(one)
        cdk = jnp.zeros((num_docs, k), count_dtype).at[doc, z].add(one)
        ck = jnp.zeros((k,), count_dtype).at[z].add(one)
        scale = jnp.asarray(SCALE, dtype)
        vbeta = jnp.asarray(beta * vocab_size, dtype)
        beta_ = jnp.asarray(beta, dtype)

        def target(t):
            ex = (t == z).astype(dtype)
            return ((cdk[doc, t].astype(dtype) - ex + alpha[t])
                    * (ckt[word, t].astype(dtype) - ex + beta_)
                    / (ck[t].astype(dtype) - ex + vbeta))

        def propose(u1, u2, cnt, start, tokens, prior_total):
            take = u1 * (scale * cnt.astype(dtype) + prior_total) \
                < scale * cnt.astype(dtype)
            occ = start + jnp.minimum((u2 * cnt).astype(jnp.int32),
                                      jnp.maximum(cnt - 1, 0))
            uni = jnp.minimum((u2 * k).astype(jnp.int32), k - 1)
            return jnp.where(take, z[tokens[occ]], uni)

        uw = jnp.asarray(k * qb, dtype)
        ud = jnp.sum(qa)
        cur = z
        keys = jax.random.split(key, 6 * cycles)
        u = [jax.random.uniform(kk, (n,)) for kk in keys]
        ident = jnp.arange(n, dtype=jnp.int32)
        for c in range(cycles):
            for half in range(2):
                u1, u2, ua = u[6 * c + 3 * half: 6 * c + 3 * half + 3]
                if half == 0:
                    prop = propose(u1, u2, w_count[word], w_start[word],
                                   by_word, uw)
                    qn = scale * ckt[word, prop].astype(dtype) + qb
                    qo = scale * ckt[word, cur].astype(dtype) + qb
                else:
                    prop = propose(u1, u2, d_count[doc], d_start[doc],
                                   ident, ud)
                    qn = scale * cdk[doc, prop].astype(dtype) + qa[prop]
                    qo = scale * cdk[doc, cur].astype(dtype) + qa[cur]
                ratio = target(prop) * qo / (target(cur) * qn)
                cur = jnp.where(ua.astype(dtype) < ratio, prop, cur)
        d = jnp.ones((n,), count_dtype)
        ckt = ckt.at[word, z].add(-d).at[word, cur].add(d)
        cdk = cdk.at[doc, z].add(-d).at[doc, cur].add(d)
        ck = ck.at[z].add(-d).at[cur].add(d)
        return cur, ckt, cdk, ck

    out = run(key, jnp.asarray(word), jnp.asarray(doc), jnp.asarray(z),
              jnp.asarray(by_word), jnp.asarray(w_count, jnp.int32),
              jnp.asarray(w_start), jnp.asarray(d_count, jnp.int32),
              jnp.asarray(d_start))
    return tuple(np.asarray(x) for x in out)


# ---------------------------------------------------------------------------
# Serving: the seed contract and fold-in replay
# ---------------------------------------------------------------------------

def model_fingerprint(ckt, ck, alpha, beta) -> str:
    """Content identity of a frozen model under the serving seed contract:
    the first 16 hex digits of sha256 over the shape, ``ckt`` (int32),
    ``ck`` (int32), ``alpha`` (float32) and ``beta`` (float64)."""
    ckt = np.ascontiguousarray(ckt, np.int32)
    h = hashlib.sha256()
    h.update(np.asarray([ckt.shape[0], ckt.shape[1], 0],
                        np.int64).tobytes())
    h.update(ckt.tobytes())
    h.update(np.ascontiguousarray(ck, np.int32).tobytes())
    h.update(np.ascontiguousarray(alpha, np.float32).tobytes())
    h.update(np.float64(beta).tobytes())
    return h.hexdigest()[:16]


def request_draws(seed: int, fingerprint: str, tokens, num_topics: int,
                  sweeps: int):
    """``(canonical tokens, z0, u)`` of one request: the tokens sorted, and
    the draws seeded by (seed, model fingerprint, sha256 of the sorted
    tokens) as the serving seed contract states."""
    canon = np.sort(np.asarray(tokens, np.int32).ravel())
    digest = hashlib.sha256(canon.tobytes()).digest()[:16]
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFFFFFFFFFF, int(fingerprint, 16),
         int.from_bytes(digest, "big")]))
    z0 = rng.integers(0, num_topics, size=canon.size).astype(np.int32)
    u = rng.random((sweeps, canon.size), dtype=np.float32)
    return canon, z0, u


def bf16_round(x) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), held in float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return b.view(np.float32)


# rounding after every operation, by name of the precision
ROUND = {"f64": lambda x: np.asarray(x, np.float64),
         "f32": lambda x: np.asarray(x, np.float32),
         "bf16": bf16_round}


def word_term(ckt_rows, ck, beta, vbeta, prec: str = "f64"):
    """``φ`` rows ``(C_wk + β)/(C_k + Vβ)``, each operation rounded to
    ``prec``."""
    r = ROUND[prec]
    num = r(r(ckt_rows) + r(beta))
    den = r(r(ck) + r(vbeta))
    return r(num / den[None, :])


def _masses(phi, alpha, cdk, z, r=ROUND["f64"]):
    """Draw masses in CDF order ``[doc part | prior part]``, ``[n, 2K]``,
    against doc counts ``cdk`` [K] (or [n, K], a row per token)."""
    cdk = np.atleast_2d(cdk)           # one doc's row, or one per token
    k = cdk.shape[1]
    own = np.arange(k)[None, :] == np.asarray(z)[:, None]
    doc = r(phi * r(cdk - own))
    doc = np.where(cdk > 0, np.maximum(doc, 0), 0)
    return np.concatenate([r(doc), r(phi * r(alpha)[None, :])], axis=1)


def foldin_outcomes(phi, alpha, z0, u, cap: int = 256,
                    spread: str = "any_order"):
    """Every final doc-count vector a float32 implementation of the draw
    rule can reach from ``z0`` with uniforms ``u`` [sweeps, n]: the set of
    ``bytes`` of the int64 counts, or ``None`` past ``cap`` paths.

    A draw branches where ``x = u·total`` lies within
    ``2ε((j + 4)·B_j + 4·total)`` of boundary ``B_j`` (``j`` its place in
    the CDF order): a bound on the rounding of any float32 summation order
    of the masses before it and of ``x``."""
    k = phi.shape[1]
    phi = np.asarray(phi, np.float64)
    alpha = np.asarray(alpha, np.float64)
    paths = [np.asarray(z0, np.int64)]
    for u_s in np.asarray(u, np.float64):
        nxt, seen = [], set()
        for z in paths:
            m = _masses(phi, alpha, np.bincount(z, minlength=k), z)
            b = np.cumsum(m, axis=1)
            total = b[:, -1:]
            x = u_s[:, None] * total
            tol = 2 * F32_EPS * (SPREAD[spread](np.cumsum(m > 0, axis=1))
                                 * b + 4 * total)
            b_prev = np.concatenate([np.zeros_like(total), b[:, :-1]], 1)
            t_prev = np.concatenate([4 * 2 * F32_EPS * total, tol[:, :-1]],
                                    1)
            cand = (m > 0) & (b_prev - t_prev <= x) & (x < b + tol)
            choices = [np.unique(np.nonzero(row)[0] % k) for row in cand]
            combos = [z.copy()]
            for i, ch in enumerate(choices):
                if ch.size == 1:
                    for c in combos:
                        c[i] = ch[0]
                else:
                    combos = [np.concatenate([c[:i], [t], c[i + 1:]])
                              for c in combos for t in ch]
                if len(combos) > cap:
                    return None
            for c in combos:
                key = c.tobytes()
                if key not in seen:
                    seen.add(key)
                    nxt.append(c)
        if len(nxt) > cap:
            return None
        paths = nxt
    return {np.bincount(z, minlength=k).tobytes() for z in paths}


def foldin_verdict(phi, alpha, z0, u, c, typical_cap: int,
                   path_cap: int) -> str:
    """``"explained"``, ``"unexplained"`` or ``"unverified"``: whether the
    doc counts ``c`` of an answer are reachable by the draw rule.  A cheap
    pass branches only within the rounding that float32 sums reach in
    practice (``spread="typical"``); an answer it does not explain goes to
    one that branches within the rounding of any summation order.  An
    answer outside that complete set is unexplained; one that neither pass
    decides because the second grew past ``path_cap`` paths is
    unverified."""
    outs = foldin_outcomes(phi, alpha, z0, u, cap=typical_cap,
                           spread="typical")
    if outs is not None and c.tobytes() in outs:
        return "explained"
    outs = foldin_outcomes(phi, alpha, z0, u, cap=path_cap,
                           spread="any_order")
    if outs is None:
        return "unverified"
    return "explained" if c.tobytes() in outs else "unexplained"


def foldin_verdicts(items, ck, alpha, beta, vbeta, typical_cap: int,
                    path_cap: int) -> list:
    """:func:`foldin_verdict` of each ``(ckt rows, z0, u, c)`` in ``items``
    (``c`` ``None`` for an answer that is no count vector at all)."""
    return ["unexplained" if c is None else
            foldin_verdict(word_term(rows, ck, beta, vbeta), alpha, z0, u, c,
                           typical_cap, path_cap)
            for rows, z0, u, c in items]


def foldin_answers(phis, alpha, z0s, us, prec: str, chunk: int = 256):
    """Fold-ins by the draw rule with every mass, running sum and product
    rounded to ``prec`` (the control), for requests with word-term rows
    ``phis[i]`` [n_i, K], ``z0s[i]`` [n_i] and ``us[i]`` [sweeps, n_i];
    returns each ``θ`` [K].  The requests of a chunk share each numpy
    operation."""
    out = []
    for c0 in range(0, len(phis), chunk):
        out += _foldin_chunk(phis[c0:c0 + chunk], alpha, z0s[c0:c0 + chunk],
                             us[c0:c0 + chunk], prec)
    return out


def _foldin_chunk(phis, alpha, z0s, us, prec):
    r = ROUND[prec]
    q, k = len(phis), phis[0].shape[1]
    owner = np.repeat(np.arange(q), [len(z) for z in z0s])
    phi = r(np.concatenate(phis))
    z = np.concatenate(z0s).astype(np.int64)

    def counts(z):
        return np.bincount(owner * k + z, minlength=q * k).reshape(q, k)

    for u_s in np.concatenate(us, axis=1):
        m = _masses(phi, alpha, counts(z)[owner], z, r)
        if prec == "bf16":
            b = np.empty_like(m)
            acc = r(np.zeros(m.shape[0]))
            for j in range(m.shape[1]):
                acc = r(acc + m[:, j])
                b[:, j] = acc
        else:                        # add.accumulate: a left-to-right chain
            b = np.cumsum(m, axis=1, dtype=m.dtype)
        x = r(r(u_s) * b[:, -1])
        pick = np.minimum((b <= x[:, None]).sum(axis=1),
                          (b < b[:, -1:]).sum(axis=1))
        z = pick % k
    return [theta(c, alpha) for c in counts(z)]


def theta(cdk, alpha) -> np.ndarray:
    cdk = np.asarray(cdk, np.float64)
    alpha = np.broadcast_to(np.asarray(alpha, np.float64), cdk.shape)
    return (cdk + alpha) / (cdk.sum() + alpha.sum())


def counts_of_theta(th, n: int, alpha):
    """The doc counts behind an answer ``θ`` of a ``n``-token query, or
    ``None`` when ``θ`` is not of the form ``(C + α)/(n + Σα)`` (to 1e-7,
    which admits an ``α`` held in float32)."""
    alpha = np.broadcast_to(np.asarray(alpha, np.float64), th.shape)
    c = np.rint(np.asarray(th, np.float64) * (n + alpha.sum()) - alpha)
    if (c < 0).any() or c.sum() != n or not np.allclose(
            theta(c, alpha), th, rtol=0, atol=1e-7):
        return None
    return c.astype(np.int64)
