#!/usr/bin/env python3
"""Record the small TPU trace that ``bench/tests/test_xplane.py`` reduces:
two jitted programs under named scopes, run inside a ``bench.window``
span, with a host sleep between them that leaves the device idle.

    python3 bench/tools/record_test_trace.py bench/tests/data/tiny_tpu
"""
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from harness import common  # noqa: E402


def main():
    out = sys.argv[1]
    jax = common.start_jax(False, 1)
    import jax.numpy as jnp

    @jax.jit
    def step_a(x):
        with jax.named_scope("scope_a"):
            return jnp.sin(x) @ x

    @jax.jit
    def step_b(x):
        with jax.named_scope("scope_b"):
            return jnp.cumsum(x * 2.0, axis=0)

    x = jnp.ones((1024, 1024), jnp.float32)
    step_a(x).block_until_ready()
    step_b(x).block_until_ready()
    tmp = out + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with common.span("bench.window"):
        for _ in range(3):
            with common.span("bench.step"):
                step_a(x).block_until_ready()
            with common.span("bench.sleep"):
                time.sleep(0.02)
            with common.span("bench.step"):
                step_b(x).block_until_ready()
    jax.profiler.stop_trace()
    from harness import xplane
    src = xplane.newest_file(tmp)
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    shutil.copy(src, out + ".xplane.pb")
    shutil.rmtree(tmp)
    print(out + ".xplane.pb", os.path.getsize(out + ".xplane.pb"))


if __name__ == "__main__":
    main()
