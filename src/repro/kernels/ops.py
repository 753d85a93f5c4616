"""Jitted public wrappers around the Pallas kernels.

Handles padding to tile boundaries, platform selection (interpret mode off
TPU), the word-grouped token layout, and the engine-facing samplers that
plug into ``core.engine``: ``sweep_block_pallas`` (exact
Gibbs-conditional kernel) and the fused alias-MH cycle pair
``sweep_block_mh_pallas`` / ``sweep_block_mh_pallas_tables`` (round vs
iteration table lifetime, DESIGN.md §10).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.alias import unpack_tables
from repro.core.mh import (DEFAULT_MH_CYCLES, block_proposal_tables,
                           uniform_streams)
from repro.core.sampler import (fold_token_deltas, map_token_chunks,
                                 token_chunk)
from repro.kernels.gibbs_conditional import (TILE_G, gibbs_conditional_call,
                                             tile_rows)
from repro.kernels.mh_alias import mh_cycle_call


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode off the TPU, compiled Mosaic kernels on it.  An
    explicit ``interpret=True`` on a TPU backend is refused: it would run
    the kernels in the Python interpreter at chip-scale shapes."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend; the Pallas "
                         "kernels compile there (leave interpret unset)")
    return bool(interpret)


# f32 [rows, K] buffers live in one grid step of each kernel's one-token-
# per-group layout: 2 × the [rows, K] input blocks (double buffering) plus
# the kernel's [rows, K] temporaries (iota, masks, mass, prefix-sum steps).
GIBBS_ROW_ARRAYS = 2 * 2 + 8
MH_ROW_ARRAYS = 2 * 8 + 8


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("tile_g", "interpret"))
def gibbs_conditional(ckt_group, cdk_rows, z_old, u, mask, ck, alpha,
                      beta, vbeta, tile_g: int | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """Padded, platform-aware kernel call.  Shapes: see kernel docstring.

    Padding guarantees zero mass on fake topics (α/C_d^k pads are 0) and
    no-ops on fake tokens (mask pads are 0), so results are unaffected.
    One-token groups (``Tg = 1``) go to the kernel as ``[G, K]`` rows.
    ``tile_g`` defaults to :data:`TILE_G` for word groups and to
    :func:`tile_rows` for one-token groups.
    """
    interpret = resolve_interpret(interpret)
    g0, t0 = z_old.shape
    k0 = ck.shape[0]
    cdk_rows = cdk_rows.astype(jnp.float32)
    if t0 == 1:
        cdk_rows = cdk_rows[:, 0, :]
    kp = -(-k0 // 128) * 128
    if tile_g is None:
        tile_g = (tile_rows(GIBBS_ROW_ARRAYS * 4 * kp) if t0 == 1
                  else TILE_G)
    ckt_group = _pad_to(_pad_to(ckt_group.astype(jnp.float32), 1, 128), 0, tile_g)
    cdk_rows = _pad_to(_pad_to(cdk_rows, cdk_rows.ndim - 1, 128), 0, tile_g)
    z_old_p = _pad_to(z_old, 0, tile_g)
    u_p = _pad_to(u, 0, tile_g)
    mask_p = _pad_to(mask.astype(jnp.int32), 0, tile_g)
    ck_p = _pad_to(ck.astype(jnp.float32), 0, 128)
    alpha_p = _pad_to(alpha.astype(jnp.float32), 0, 128)
    out = gibbs_conditional_call(ckt_group, cdk_rows, z_old_p, u_p, mask_p,
                                 ck_p, alpha_p, beta, vbeta, k_real=k0,
                                 tile_g=tile_g, interpret=interpret)
    return out[:g0, :t0]


def group_tokens_by_word(word_off: np.ndarray, group_width: int
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host helper: chunk word-sorted tokens into ``[G, Tg]`` word groups.

    ``word_off`` must be sorted (inverted-index order).  Each group holds up
    to ``group_width`` tokens of ONE word; long postings split into several
    groups (each still word-pure, so the per-group coeff cache stays exact).

    Returns (group_word [G], position [G, Tg] indices into the token array,
    mask [G, Tg]).
    """
    word_off = np.asarray(word_off)
    n = word_off.shape[0]
    groups_w, groups_pos = [], []
    i = 0
    while i < n:
        w = word_off[i]
        j = i
        while j < n and word_off[j] == w and j - i < group_width:
            j += 1
        groups_w.append(int(w))
        groups_pos.append(np.arange(i, j))
        i = j
    g = max(len(groups_w), 1)
    gw = np.zeros(g, np.int32)
    pos = np.zeros((g, group_width), np.int32)
    msk = np.zeros((g, group_width), bool)
    for gi, (w, p) in enumerate(zip(groups_w, groups_pos)):
        gw[gi] = w
        pos[gi, :len(p)] = p
        msk[gi, :len(p)] = True
    return gw, pos, msk


@jax.jit
def sweep_block_pallas(cdk, ckt_block, ck, doc, word_off, z, mask, u,
                       alpha, beta, vbeta):
    """Engine-facing sampler: same signature/semantics as
    ``core.sampler.sweep_block_batched`` but with the conditional evaluated
    by the Pallas kernel (token-per-group layout; the word-grouped layout is
    exercised by ``gibbs_conditional`` directly in the tests).

    Bit-identical to the ``batched`` sampler mode given the same uniforms —
    asserted by tests — so the kernel slots into the model-parallel engine
    without changing its convergence behaviour.  The gathered ``[C, K]``
    rows are live :func:`token_chunk` tokens at a time.
    """
    k = ck.shape[0]
    ck_f = ck.astype(jnp.float32)

    def draw(doc_c, t_c, z_c, mask_c, u_c):
        return gibbs_conditional(
            ckt_block[t_c].astype(jnp.float32),              # [C, K]
            cdk[doc_c].astype(jnp.float32)[:, None, :],      # [C, 1, K]
            z_c[:, None], u_c[:, None], mask_c[:, None], ck_f, alpha,
            beta, vbeta)[:, 0]

    z_new = map_token_chunks(draw, (doc, word_off, z, mask, u),
                             token_chunk(k, 4))
    z_new = jnp.where(mask, z_new, z)
    return fold_token_deltas(cdk, ckt_block, ck, doc, word_off, z, z_new,
                             mask)


def _mh_cycle_pallas_core(cdk, ckt_block, ck, doc, word_off, z, mask, u,
                          alpha, beta, vbeta, word_table, doc_table,
                          num_cycles, interpret):
    """Shared fused-kernel body: gather the per-token operand rows, run
    the FULL MH cycle in one ``mh_cycle_call``, fold count deltas.

    Token-per-group degenerate layout (like ``sweep_block_pallas``): the
    per-token row gathers materialize ``[C, K]`` operands for each chunk
    of ``C`` tokens, so this path trades memory for exercising the kernel
    end-to-end.  The word-grouped [G, Tg > 1] VMEM-reuse layout the
    kernel is designed around is exercised on ``mh_cycle_call`` directly
    by ``tests/test_alias.py::test_mh_cycle_kernel_word_grouped_layout``
    (multi-tile grid, bit-checked against the jnp cycle).
    """
    k0 = ck.shape[0]
    kp = -(-k0 // 128) * 128
    ckt_f = ckt_block.astype(jnp.float32)
    cdk_f = cdk.astype(jnp.float32)
    ck_f = _pad_to(ck.astype(jnp.float32), 0, 128)
    alpha_f = _pad_to(alpha.astype(jnp.float32), 0, 128)
    wcut, walias, wu, wmass = word_table
    dcut, dalias, du, dmass = doc_table
    # pads are never drawn: the alias cell index is clamped to the REAL K
    # inside the kernel
    tile_g = tile_rows(MH_ROW_ARRAYS * 4 * kp)

    def draw(doc_c, t_c, z_c, mask_c, streams_c):
        rows = lambda x, r: _pad_to(_pad_to(x[r], 1, 128), 0, tile_g)
        col = lambda x: _pad_to(x, 0, tile_g)[:, None]
        t = z_c.shape[0]
        return mh_cycle_call(
            rows(wcut, t_c), rows(walias, t_c),
            rows(wmass.astype(jnp.float32), t_c), col(wu[t_c]),
            rows(dcut, doc_c), rows(dalias, doc_c),
            rows(dmass.astype(jnp.float32), doc_c), col(du[doc_c]),
            rows(ckt_f, t_c), rows(cdk_f, doc_c), col(z_c),
            _pad_to(streams_c.T, 1, tile_g)[:, :, None],
            col(mask_c.astype(jnp.int32)), ck_f, alpha_f,
            beta, vbeta, k_real=k0, num_cycles=num_cycles,
            tile_g=tile_g, interpret=interpret)[:t, 0]

    streams = uniform_streams(u, 4 * num_cycles)            # [4c, T]
    z_new = map_token_chunks(draw, (doc, word_off, z, mask, streams.T),
                             token_chunk(k0, 10))
    z_new = jnp.where(mask, z_new, z)
    return fold_token_deltas(cdk, ckt_block, ck, doc, word_off, z, z_new,
                             mask)


@functools.partial(jax.jit, static_argnames=("num_cycles", "interpret"))
def sweep_block_mh_pallas(cdk, ckt_block, ck, doc, word_off, z, mask, u,
                          alpha, beta, vbeta,
                          num_cycles: int = DEFAULT_MH_CYCLES,
                          interpret: bool | None = None):
    """Engine-facing alias-MH sampler with the WHOLE cycle — word
    proposal, doc proposal, both acceptances, all ``num_cycles`` times —
    fused into one Pallas kernel (``kernels/mh_alias.py``).  Same
    signature/semantics as ``core.mh.sweep_block_mh`` (round table
    lifetime: tables built fresh per call, shared prologue) and
    bit-identical to it given the same uniforms (asserted by tests), so
    the kernel slots into the engine without changing the chain's
    distribution.
    """
    interpret = resolve_interpret(interpret)
    word_table, doc_table = block_proposal_tables(cdk, ckt_block, alpha,
                                                  beta)
    return _mh_cycle_pallas_core(cdk, ckt_block, ck, doc, word_off, z,
                                 mask, u, alpha, beta, vbeta, word_table,
                                 doc_table, num_cycles, interpret)


@functools.partial(jax.jit, static_argnames=("num_cycles", "interpret"))
def sweep_block_mh_pallas_tables(cdk, ckt_block, ck, doc, word_off, z,
                                 mask, u, alpha, beta, vbeta,
                                 word_packed, doc_packed,
                                 num_cycles: int = DEFAULT_MH_CYCLES,
                                 interpret: bool | None = None):
    """Table-aware form of :func:`sweep_block_mh_pallas` (iteration table
    lifetime, DESIGN.md §10): consumes the engine's packed traveling word
    table and per-iteration doc table instead of building its own — the
    fused-cycle analogue of ``core.mh.sweep_block_mh_tables`` and
    bit-identical to it given the same uniforms and tables.
    """
    interpret = resolve_interpret(interpret)
    return _mh_cycle_pallas_core(cdk, ckt_block, ck, doc, word_off, z,
                                 mask, u, alpha, beta, vbeta,
                                 unpack_tables(word_packed),
                                 unpack_tables(doc_packed), num_cycles,
                                 interpret)


@functools.partial(jax.jit, static_argnames=("dcap", "wcap", "interpret"))
def sweep_block_sparse_pallas(cdk, ckt_block, ck, doc, word_off, z, mask,
                              u, alpha, beta, vbeta, dcap: int,
                              wcap: int, interpret: bool | None = None):
    """Engine-facing hybrid sparse sampler with the lane block — segment
    masses, prefix sums, counted draws, segment select — run in the
    Pallas kernel (``kernels/sparse_gibbs.py``).  Same signature and
    frozen-count semantics as ``core.sparse_device.sweep_block_sparse``
    and bit-identical to it given the same uniforms (asserted by tests):
    the round-frozen layout, the per-token gathers and the dense-segment
    pick are the SHARED jnp functions, and the kernel mirrors the jnp
    lane block op for op.
    """
    from repro.core.sparse_device import sparse_sweep
    from repro.kernels.sparse_gibbs import sparse_lane_call
    interpret = resolve_interpret(interpret)

    def lane_draw(ops, z_c, mask_c, u_c):
        return sparse_lane_call(ops["wops"], ops["dops"], ops["h_t"], z_c,
                                mask_c, u_c, ops["sdense"], beta, vbeta,
                                interpret=interpret)

    return sparse_sweep(lane_draw, cdk, ckt_block, ck, doc, word_off, z,
                        mask, u, alpha, beta, vbeta, dcap, wcap)
