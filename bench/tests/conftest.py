"""The benchmark's own tests, run by hand on the CPU:

    python -m pytest bench/tests -q

They import the harness from ``bench/`` and start JAX on the CPU with
the rehearsal's faked devices, as ``bench/run.py --rehearse`` does."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


@pytest.fixture(scope="session")
def jax():
    from harness import common
    return common.start_jax(True, 1)
