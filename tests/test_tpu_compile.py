"""The Pallas kernels compile for a TPU v5e, at the engine's real widths.

Interpret-mode tests cannot see what Mosaic refuses (an op it cannot
lower, a grid step that outgrows VMEM).  These tests compile each kernel
through its wrapper with ``interpret=False`` for a described, unattached
``v5e:2x2`` topology, in the engine's one-token-per-group layout, at
K = 1024 (pubmed-k1000) and K = 10240 (the K = 10^4 configs padded to the
lane boundary).  Nothing runs; a compile that passes is not a chip run.
The alias-table builder, plain XLA and no kernel, is compiled beside
them: its row sorts along K are what the MH tables rest on.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.alias import build_alias_int_rows
from repro.core.mh import DEFAULT_MH_CYCLES
from repro.kernels import ops
from repro.kernels.sparse_gibbs import sparse_lane_call

TOKENS = 512
ROWS = 64
KS = [1024, 10240]


@pytest.fixture(scope="module")
def topo():
    # the TPU library otherwise writes its logs under /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _spec(sharding):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


@pytest.mark.parametrize("k", KS)
def test_gibbs_conditional_compiles(one_chip, k):
    s, t = _spec(one_chip), TOKENS
    _compile(functools.partial(ops.gibbs_conditional, interpret=False),
             (s((t, k)), s((t, 1, k)), s((t, 1), jnp.int32), s((t, 1)),
              s((t, 1), jnp.int32), s((k,)), s((k,)), 0.01, 10.0))


@pytest.mark.parametrize("k", KS)
def test_mh_cycle_compiles(one_chip, k):
    s, t, i32 = _spec(one_chip), TOKENS, jnp.int32
    _compile(functools.partial(ops.sweep_block_mh_pallas_tables,
                               num_cycles=DEFAULT_MH_CYCLES,
                               interpret=False),
             (s((ROWS, k), i32), s((ROWS, k), i32), s((k,), i32),
              s((t,), i32), s((t,), i32), s((t,), i32), s((t,), jnp.bool_),
              s((t,)), s((k,)), s(()), s(()),
              s((3, ROWS, k), i32), s((3, ROWS, k), i32)))


def test_sparse_lane_compiles(one_chip):
    # the lane kernel's shapes depend on the lane caps, not on K
    s, t, i32 = _spec(one_chip), TOKENS, jnp.int32
    wcap, dcap = 32, 133              # pubmed's longest docs: Poisson(90)

    def lane_ops(cap):
        return {"kk": s((t, cap), i32), "valid": s((t, cap), jnp.bool_),
                "ckt": s((t, cap)), "cdk": s((t, cap)), "ck": s((t, cap)),
                "alpha": s((t, cap))}

    _compile(functools.partial(sparse_lane_call, interpret=False),
             (lane_ops(wcap), lane_ops(dcap), s((t,), jnp.bool_),
              s((t,), i32), s((t,), jnp.bool_), s((t,)), s((t,)),
              s(()), s(())))


@pytest.mark.parametrize("k", KS)
def test_alias_table_build_compiles(one_chip, k):
    text = build_alias_int_rows.lower(
        _spec(one_chip)((ROWS, k), jnp.int32)).compile().as_text()
    assert " sort(" in text and " scatter(" not in text
