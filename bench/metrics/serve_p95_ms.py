"""95th percentile of the latency of every request of the window, from
its scheduled arrival to its response.  A refused or failed request
counts as ``harness.serve.MISSED_S``, above any limit."""
import numpy as np


def read(run):
    return float(np.percentile(np.asarray(run.latencies_s), 95)) * 1e3
