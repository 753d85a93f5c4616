"""Model-parallel LDA engine package (DESIGN.md §2–§3).

Layout:

* ``state.py``    — :class:`MPState` (slot-queue per-worker state) plus
  layout construction, initialization, and gather/observation helpers;
* ``rounds.py``   — the per-(worker, round) sampling step and the
  sampler registry;
* ``backends.py`` — one per-worker iteration over the hybrid 2D
  ``(data, model)`` grid (DESIGN.md §8), placed by named-axis ``vmap``
  on one device or by ``shard_map`` one worker per device;
* ``reference.py`` — the FROZEN pre-2D 1D implementation, kept only as
  the bit-exactness anchor for ``tests/test_engine_2d.py``; harness-only,
  deliberately NOT re-exported here;
* ``api.py``      — the :class:`ModelParallelLDA` facade.
"""
from repro.core.engine.api import ModelParallelLDA
from repro.core.engine.backends import (iteration_vmap,
                                        make_shard_map_iteration)
from repro.core.engine.rounds import (available_samplers,
                                      register_sampler,
                                      register_table_sampler,
                                      resolve_sampler,
                                      resolve_table_sampler, table_capable,
                                      worker_round, worker_round_tables)
from repro.core.engine.state import EngineLayout, MPState

__all__ = [
    "EngineLayout", "ModelParallelLDA", "MPState", "available_samplers",
    "iteration_vmap", "make_shard_map_iteration", "register_sampler",
    "register_table_sampler", "resolve_sampler", "resolve_table_sampler",
    "table_capable", "worker_round", "worker_round_tables",
]
