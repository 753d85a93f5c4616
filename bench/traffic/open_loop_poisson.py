"""Open-loop Poisson arrivals of distinct fold-in queries.

``round(rate_qps · seconds)`` requests.  Gaps are the exponential law's
mid-quantiles at ``rate_qps`` in one order fixed for every seed
(``ARRIVAL_ORDER``): where the bursts fall sets the tail, and a seed that
moved them would measure the arrivals rather than the server.  Lengths
are Poisson(``len_mean``) mid-quantiles, at least ``len_min``, shuffled
by the seed; word ids are Zipf(``word_zipf_a``) over the vocabulary.  No
two requests share a token multiset, so a response cache never hits.
Copied from ``repro.serve.traffic.poisson_trace`` (there with uniform
word ids and a random request count)."""
import numpy as np
from scipy import stats

from harness import gen

# the stream that orders the request gaps, the same for every run seed
ARRIVAL_ORDER = 20141108


def lengths(n: int, mix: dict) -> np.ndarray:
    """The ``n`` mid-quantiles of the length law (one multiset per ``n``)."""
    q = (np.arange(n) + 0.5) / n
    return np.maximum(stats.poisson.ppf(q, mix["len_mean"]),
                      mix["len_min"]).astype(np.int64)


def requests(seed: int, seconds: float, vocab_size: int, mix: dict):
    n = max(1, int(round(mix["rate_qps"] * seconds)))
    rng = np.random.default_rng(seed)
    q = (np.arange(n) + 0.5) / n
    order = np.random.default_rng(ARRIVAL_ORDER).permutation(n)
    t = np.cumsum((-np.log1p(-q) / mix["rate_qps"])[order])
    cdf = gen.zipf_cdf(vocab_size, mix["word_zipf_a"])
    seen, out = set(), []
    for ti, n_tok in zip(t, rng.permutation(lengths(n, mix))):
        while True:
            toks = gen.zipf_ids(rng, cdf, int(n_tok))
            key = np.sort(toks).tobytes()
            if key not in seen:
                break
        seen.add(key)
        out.append((float(ti), toks))
    return out
