"""One per-worker iteration, placed two ways (DESIGN.md §2–§3, §8).

One iteration = ``B = S·M`` rounds.  Every round each worker samples its
resident block (slot 0 of its queue), hands exactly that block to ring
neighbour ``m - 1`` (``ppermute`` — parked slots never travel), and
enqueues the received block at the tail of its queue, where it surfaces
``S`` rounds later.  At ``S = 1`` the queue degenerates to the paper's
original rotation: the received block is resident immediately.

Hybrid data×model parallelism (``data_parallel = D``, DESIGN.md §8): all
per-worker arrays carry one leading axis of length ``R = D·M`` (row
``g = d·M + m``).  The ``D`` replicas run the same model-axis rotation
over replicated copies of the ``S·M`` blocks; at every round boundary the
just-sampled resident copies are reconciled by a delta psum along the
data axis — ``block' = block_pre + Σ_d (block_d − block_pre)`` — before
they rotate, so parked copies never diverge across replicas.  This is the
AD-LDA all-reduce of ``core/data_parallel.py`` folded into the engine,
confined to the one resident ``[Vb, K]`` slice per round; at ``D = 1``
the reconciliation vanishes and the iteration is exactly the frozen 1D
reference (``engine/reference.py`` — enforced bitwise by
``tests/test_engine_2d.py``).

:func:`worker_iteration` is one grid row's whole iteration, written in
the paper's collectives only: ``ppermute`` along the model axis, the
delta ``psum`` along the data axis, ``psum`` of the ``{C_k}`` deltas over
the whole grid once per round (they drift in between, §3.3), and
``pmean`` for the drift statistic.  It is placed two ways:

* :func:`iteration_vmap` — the grid is a batch axis on one device, the
  function mapped by ``jax.vmap`` with named axes, which turn the
  collectives into rolls and sums over that axis.  Runs anywhere.
* :func:`make_shard_map_iteration` — the grid maps onto a ``(data,
  model)`` mesh; the rotation lowers to HLO ``collective-permute`` on the
  model axis and the replica reconciliation to an ``all-reduce`` on the
  data axis.  This is the production path.

There is one round body, so the two placements agree by construction.

Sampler staleness composes per block (DESIGN.md §9): the ``batched`` /
``pallas`` / ``mh`` samplers freeze block-local counts at round start,
which is exactly the window between two rotation/reconciliation
collectives — so neither the S-block pipeline nor the data axis widens
it.

Traveling tables (``table_lifetime="iteration"``, DESIGN.md §10): for
the MH family the per-block word-proposal alias table is built ONCE per
iteration — at the block's first residency, i.e. during the first ``S``
rounds — and then rotates through the ring *with* its block as one
packed int32 array (a second ``ppermute`` per round), parked in a slot
queue mirroring the block queue.  Doc-proposal tables are built once per
iteration from iteration-start ``cdk`` and are loop-invariant.  Tables
are iteration-local by construction: every table a reuse round reads was
built earlier in the same iteration, so the state pytree carries none and
checkpoints stay sampler-agnostic.  Both iteration functions donate the
state buffers (``donate_argnums``), so the big count/assignment arrays
are updated in place instead of copied.

CountStore boundary (DESIGN.md §16): the device chain keeps every slot
of ``MPState.ckt`` DENSE — jit caching, buffer donation, and the
``ppermute`` ring all want static shapes — so the pluggable store
layouts (``engine/countstore.py``) live strictly AT REST: checkpoints,
streaming block files, sharded snapshots, and the serving row loads.  The
streaming engine is where a store's layout also reaches compute, via the
store-native sampler registry (``rounds.resolve_store_sampler``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import tracing
from repro.core import schedule as sched
from repro.core.engine.rounds import (resolve_sampler,
                                      resolve_table_sampler, worker_round,
                                      worker_round_tables)
from repro.core.engine.state import MPState


def worker_iteration(cdk, ckt, blk, ck_syn, ck_loc, z, u, doc, woff, mask,
                     alpha, beta, vbeta, *, axis: str,
                     data_axis: str | None, sampler_mode: str,
                     sync_ck: bool, table_lifetime: str, track_error: bool,
                     sampler_args: tuple):
    """One grid row's whole iteration (``S·M`` rounds), un-batched.

    ``ckt`` is the row's ``[S, Vb, K]`` slot queue, ``blk`` its ``[S]``
    block ids and ``u`` its ``[B, T]`` uniforms; ``ck_syn`` is the
    grid-wide ``[K]`` totals of the last sync.  ``axis`` names the model
    axis the ring rotates along; ``data_axis`` the replica axis, or
    ``None`` for the 1D ring.  ``table_lifetime="iteration"`` selects the
    traveling-table MH schedule (module docstring).  Returns the six state
    arrays and the ``[B]`` drift statistic, all-zero when ``track_error``
    is off — with ``sync_ck`` the true totals are still computed for the
    sync.
    """
    perm = sched.rotation_permutation(jax.lax.axis_size(axis))
    ck_axes = (data_axis, axis) if data_axis is not None else axis
    tables = table_lifetime == "iteration"
    s_ = ckt.shape[0]
    if tables:
        from repro.core.mh import build_doc_tables, build_word_tables
        round_fn = partial(worker_round_tables,
                           sampler=resolve_table_sampler(sampler_mode))
        # per-iteration doc tables from iteration-start cdk (DESIGN.md
        # §10): loop-invariant across all S·M rounds.
        with jax.named_scope(tracing.DOC_TABLES):
            dtab = build_doc_tables(cdk, alpha)
    else:
        round_fn = partial(worker_round, sampler=resolve_sampler(
            sampler_mode, sampler_args))

    def round_step(carry, u_r, build=False):
        cdk, ckt, blk, ck_syn, ck_loc, z, ttab = carry
        with jax.named_scope(tracing.QUEUE):
            res_pre = ckt[0]             # [Vb, K] round-start copy
            res_blk = blk[0]
            if tables and not build:
                wtab = ttab[0]           # the table that traveled in
        if tables and build:
            # first residency of this block this iteration: build its word
            # table from the round-start copy (identical across replicas,
            # so the D builds agree bitwise).
            with jax.named_scope(tracing.WORD_TABLES):
                wtab = build_word_tables(res_pre, beta)
        with jax.named_scope(tracing.SAMPLE):
            extra = (wtab, dtab) if tables else ()
            cdk, res_ckt, ck_loc, z = round_fn(
                cdk, res_pre, res_blk, ck_loc, z, u_r, doc, woff, mask,
                alpha, beta, vbeta, *extra)
        if data_axis is not None:
            # delta-psum reconciliation of the D replica copies of the
            # resident block (DESIGN.md §8) — the only cross-replica
            # traffic, one [Vb, K] all-reduce per round.
            with jax.named_scope(tracing.RECONCILE):
                res_ckt = res_pre + jax.lax.psum(res_ckt - res_pre,
                                                 data_axis)
        # Algorithm 2 commit+request: ONLY the resident block travels —
        # per-round traffic stays one [Vb, K] block per worker (plus its
        # packed table under the iteration lifetime) no matter how large
        # S makes the total model.
        with jax.named_scope(tracing.ROTATE):
            res_ckt = jax.lax.ppermute(res_ckt, axis, perm)
            res_blk = jax.lax.ppermute(res_blk, axis, perm)
            if tables:
                wtab = jax.lax.ppermute(wtab, axis, perm)
        with jax.named_scope(tracing.QUEUE):
            ckt = jnp.concatenate([ckt[1:], res_ckt[None]], axis=0)
            blk = jnp.concatenate([blk[1:], res_blk[None]], axis=0)
            if tables:
                ttab = jnp.concatenate([ttab[1:], wtab[None]], axis=0)
        with jax.named_scope(tracing.CK_SYNC):
            # paper Fig-3 error: pre-sync ℓ1 drift of local {C_k} vs true
            # totals.  ck_true feeds the sync too, so it is only skippable
            # when neither consumer is on.
            err = jnp.float32(0.0)
            if sync_ck or track_error:
                ck_true = ck_syn + jax.lax.psum(ck_loc - ck_syn, ck_axes)
            if track_error:
                n_tok = jnp.maximum(ck_true.sum(), 1).astype(jnp.float32)
                err = jax.lax.pmean(
                    jnp.abs(ck_loc - ck_true).sum().astype(jnp.float32),
                    ck_axes) / n_tok
            if sync_ck:
                ck_loc = ck_true
                ck_syn = ck_true
        return (cdk, ckt, blk, ck_syn, ck_loc, z, ttab), err

    # table queue mirroring the block queue; never read before its slot is
    # written (every block's table is built in rounds < S), so the zero
    # init is dead weight XLA can elide.
    with jax.named_scope(tracing.QUEUE):
        ttab0 = (jnp.zeros((s_, 3) + ckt.shape[1:], jnp.int32)
                 if tables else jnp.zeros((), jnp.int32))
    carry = (cdk, ckt, blk, ck_syn, ck_loc, z, ttab0)
    if tables:
        # first S rounds build each block's table at its first residency;
        # the rest reuse the traveling payloads.
        carry, errs_b = jax.lax.scan(partial(round_step, build=True),
                                     carry, u[:s_])
        carry, errs_r = jax.lax.scan(round_step, carry, u[s_:])
        errs = jnp.concatenate([errs_b, errs_r])
    else:
        carry, errs = jax.lax.scan(round_step, carry, u)
    return (*carry[:6], errs)


@partial(jax.jit, static_argnames=("sampler_mode", "sync_ck",
                                   "data_parallel", "table_lifetime",
                                   "track_error", "sampler_args"),
         donate_argnums=(0,))
def iteration_vmap(state: MPState, u, doc, woff, mask, alpha, beta, vbeta,
                   sampler_mode: str = "scan", sync_ck: bool = True,
                   data_parallel: int = 1, table_lifetime: str = "round",
                   track_error: bool = True, sampler_args: tuple = ()):
    """One full iteration = S·M rounds, the grid stacked on one device.

    ``u`` is ``[B, R, T]`` — one uniform per (round, grid row, token slot),
    with ``R = data_parallel · M``.  The rows are reshaped data-major to
    ``(D, M)`` and :func:`worker_iteration` is mapped over them by named
    ``vmap`` axes.  ``state`` is donated: the returned :class:`MPState`
    reuses the input buffers, so callers must not touch the argument after
    the call (the facade always rebinds it).
    """
    d_ = data_parallel
    r_ = state.ckt.shape[0]
    m_ = r_ // d_
    data_axis = "data" if d_ > 1 else None
    fn = partial(worker_iteration, axis="model", data_axis=data_axis,
                 sampler_mode=sampler_mode, sync_ck=sync_ck,
                 table_lifetime=table_lifetime, track_error=track_error,
                 sampler_args=sampler_args)
    # ``vmap`` names its transform around the first scope opened under it:
    # this one, so the stage scopes stay bare path components as they are
    # under ``shard_map``.
    fn = jax.named_scope("grid")(fn)
    in_axes = (0, 0, 0, None, 0, 0, 1, 0, 0, 0, None, None, None)
    out_axes = (0, 0, 0, None, 0, 0, None)
    fn = jax.vmap(fn, in_axes, out_axes, axis_name="model")
    rows = (state.cdk, state.ckt, state.block_id, state.ck_local, state.z,
            doc, woff, mask)
    if data_axis is not None:
        fn = jax.vmap(fn, in_axes, out_axes, axis_name=data_axis)
        rows = tuple(x.reshape(d_, m_, *x.shape[1:]) for x in rows)
        u = u.reshape(u.shape[0], d_, m_, *u.shape[2:])
    cdk, ckt, blk, ck_loc, z, doc, woff, mask = rows
    cdk, ckt, blk, ck_syn, ck_loc, z, errs = fn(
        cdk, ckt, blk, state.ck_synced, ck_loc, z, u, doc, woff, mask,
        alpha, beta, vbeta)
    cdk, ckt, blk, ck_loc, z = (x.reshape(r_, *x.shape[1 + (d_ > 1):])
                                for x in (cdk, ckt, blk, ck_loc, z))
    return MPState(cdk, ckt, blk, ck_syn, ck_loc, z), errs


def row_spec(axis: str, data_axis: str | None = None) -> P:
    """The partition of every per-row array of the shard_map iteration:
    its leading ``R = D·M`` grid axis over the model ``axis``, or over
    ``(data_axis, axis)`` data-major on the 2D grid."""
    return P((data_axis, axis)) if data_axis is not None else P(axis)


def make_shard_map_iteration(mesh: Mesh, axis: str, sampler_mode: str,
                             sync_ck: bool, data_axis: str | None = None,
                             table_lifetime: str = "round",
                             track_error: bool = True,
                             sampler_args: tuple = ()):
    """Build the jitted per-device iteration function for ``mesh``.

    ``axis`` is the model axis carrying the block ring.  When ``data_axis``
    is given the mesh is 2D ``(data, model)``: per-worker arrays shard
    their leading ``R = D·M`` axis over BOTH axes (data-major, matching
    ``state.build_layout``'s row order), resident blocks are reconciled by
    a per-round delta ``psum`` along ``data``, and ``{C_k}`` syncs over
    the whole grid.  ``data_axis=None`` is the original 1D worker ring.

    With ``table_lifetime="iteration"`` the per-round ``ppermute`` of the
    resident block gains a companion: the block's packed word-proposal
    table rides the same ring permutation, so table payloads move as one
    extra ``collective-permute`` per round and never rebuild outside the
    first ``S`` rounds (module docstring; DESIGN.md §10).  The six state
    arrays are donated — counts update in place across iterations.
    """
    fn = partial(worker_iteration, axis=axis, data_axis=data_axis,
                 sampler_mode=sampler_mode, sync_ck=sync_ck,
                 table_lifetime=table_lifetime, track_error=track_error,
                 sampler_args=sampler_args)

    def per_device(cdk, ckt, blk, ck_syn, ck_loc, z, u, doc, woff, mask,
                   alpha, beta, vbeta):
        # local shards arrive with a leading grid axis of size 1
        cdk, ckt, blk, ck_loc, z = (x[0] for x in (cdk, ckt, blk, ck_loc, z))
        doc, woff, mask, u = (x[0] for x in (doc, woff, mask, u))
        cdk, ckt, blk, ck_syn, ck_loc, z, errs = fn(
            cdk, ckt, blk, ck_syn, ck_loc, z, u, doc, woff, mask,
            alpha, beta, vbeta)
        return (cdk[None], ckt[None], blk[None], ck_syn, ck_loc[None],
                z[None], errs)

    w = row_spec(axis, data_axis)
    return jax.jit(jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(w, w, w, P(), w, w, w, w, w, w, P(), P(), P()),
        out_specs=(w, w, w, P(), w, w, P()),
        check_vma=False), donate_argnums=(0, 1, 2, 3, 4, 5))
