"""shard_map backend == vmap backend, bit-exact, on 4 simulated devices.

Runs in a subprocess because the device count must be fixed before JAX
initializes (and the rest of the suite must keep seeing 1 device).
"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.data.synthetic import synthetic_corpus
from repro.core.engine import ModelParallelLDA

corpus, _, _ = synthetic_corpus(num_docs=40, vocab_size=120, num_topics=8,
                                doc_len=30, seed=0)
a = ModelParallelLDA(corpus, 8, 4, seed=1, backend="vmap")
b = ModelParallelLDA(corpus, 8, 4, seed=1, backend="shard_map")
for _ in range(2):
    a.step(); b.step()
sa, sb = a.gather_counts(), b.gather_counts()
assert (np.asarray(sa.ckt) == np.asarray(sb.ckt)).all(), "ckt mismatch"
assert (np.asarray(sa.cdk) == np.asarray(sb.cdk)).all(), "cdk mismatch"
assert (a.assignments() == b.assignments()).all(), "z mismatch"
assert np.allclose(a.round_errors, b.round_errors, atol=1e-6), "errs mismatch"
print("SHARD_MAP_OK")
"""


@pytest.mark.slow
def test_shard_map_equals_vmap_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARD_MAP_OK" in out.stdout
