"""Dry-run of the model-parallel LDA engine itself at PAPER scale on the
production mesh — the reproduction's §Roofline row for the paper's own
workload.

    PYTHONPATH=src python -m repro.launch.lda_dryrun --config wiki-unigram-k5000
    PYTHONPATH=src python -m repro.launch.lda_dryrun --all
    PYTHONPATH=src python -m repro.launch.lda_dryrun --blocks-per-worker 4
    PYTHONPATH=src python -m repro.launch.lda_dryrun --data-parallel 8

Lowers one full iteration (S·M rounds: sample resident block -> ppermute
resident block -> psum C_k) of the shard_map engine against
ShapeDtypeStruct state at the paper's V/K/token counts, on a 64-worker
ring (the paper's Table-1 cluster) mapped onto v5e chips, and reports
memory per worker, collective bytes (the block-rotation traffic), and
roofline terms.  ``--blocks-per-worker`` (S) pipelines ``S·M`` vocabulary
blocks through the ring, shrinking the resident block ``S``-fold
(DESIGN.md §3).
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

# ruff: noqa: E402
import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.lda_paper import LDA_CONFIGS
from repro.core.engine.backends import \
    make_shard_map_iteration as _iteration_shard_map
from repro.core.schedule import partition_vocab
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_lda_mesh
from repro.roofline import analysis as roofline


def run(cfg_name: str, workers: int = 64, sampler: str = "batched",
        out_dir: str = "dryrun_out",
        blocks_per_worker: int = 1, data_parallel: int = 1) -> dict:
    cfg = LDA_CONFIGS[cfg_name]
    m, k = workers, cfg.num_topics
    sb, dp = blocks_per_worker, data_parallel
    b = sb * m                          # total vocabulary blocks
    r = dp * m                          # worker-grid rows (data × model)
    part = partition_vocab(cfg.vocab_size, b)
    vb = part.block_size
    dloc = -(-cfg.num_docs // r)
    # per-(grid row, block) token capacity with a 1.2 load-imbalance factor
    cap = max(int(cfg.num_tokens / (r * b) * 1.2), 1)
    mesh = make_lda_mesh(m, data_parallel=dp)

    s = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt)
    state = dict(
        cdk=s((r, dloc, k)), ckt=s((r, sb, vb, k)), blk=s((r, sb)),
        ck_syn=s((k,)), ck_loc=s((r, k)), z=s((r, b, cap)),
        u=s((r, b, cap), jnp.float32), doc=s((r, b, cap)),
        woff=s((r, b, cap)), mask=s((r, b, cap), jnp.bool_),
        alpha=s((k,), jnp.float32), beta=s((), jnp.float32),
        vbeta=s((), jnp.float32),
    )
    sampler_args = ()
    if sampler in ("sparse", "sparse_pallas"):
        # shape-derived caps, like the engine facade: a dryrun has no
        # corpus, so the per-row token capacity bounds the doc nonzeros
        from repro.core.sparse_device import default_sparse_args
        sampler_args = default_sparse_args(k, cap)
    fn = _iteration_shard_map(mesh, "w", sampler, sync_ck=True,
                              data_axis="data" if dp > 1 else None,
                              sampler_args=sampler_args)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*state.values())
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    costs = roofline.raw_costs(compiled)
    # the round scan body (1 of S·M rounds) is counted once: scale by S·M
    costs.flops *= b
    costs.bytes_accessed *= b
    costs.coll_bytes *= b
    for key in costs.coll_detail["bytes"]:
        costs.coll_detail["bytes"][key] *= b
    terms = roofline.roofline_terms(costs)
    block_bytes = vb * k * 4
    rec = {
        "workload": cfg_name, "workers": m, "sampler": sampler,
        "blocks_per_worker": sb, "num_blocks": b,
        "data_parallel": dp, "grid_rows": r,
        "model_variables": cfg.model_variables,
        "block_shape": [vb, k],
        "block_bytes": block_bytes,
        "resident_block_bytes_per_worker": block_bytes,
        "memory": {
            "argument_bytes_per_device": int(ma.argument_size_in_bytes),
            "temp_bytes_per_device": int(ma.temp_size_in_bytes),
            "total_gib_per_device": round(
                (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 2**30,
                3),
        },
        "costs_per_iteration": {
            "flops_per_device": costs.flops,
            "bytes_per_device": costs.bytes_accessed,
            "collective_bytes_per_device": costs.coll_bytes,
            "collective_detail": costs.coll_detail["bytes"],
        },
        "roofline": terms,
        # paper's communication claim: per-iteration traffic per worker is
        # S·M block moves (one RESIDENT block per round) + 2K-vector syncs
        # — O(V·K/(S·M)) per round regardless of M or S, vs O(M·V·K) for
        # DP gossip; parked blocks never travel.
        "analytic_rotation_bytes_per_iter": b * block_bytes,
        # hybrid grid (DESIGN.md §8): the per-round delta psum along data
        # moves one resident block per worker per round — same order as
        # the rotation, and zero when D = 1
        "analytic_data_psum_bytes_per_iter": (b * block_bytes
                                              if dp > 1 else 0),
        "status": "ok",
    }
    os.makedirs(out_dir, exist_ok=True)
    tag = f"ring{m}x{sb}" if dp == 1 else f"grid{dp}x{m}x{sb}"
    # the sampler is part of the artifact identity: different samplers
    # lower to very different rooflines and must not clobber each other
    if sampler != "batched":
        tag = f"{tag}__{sampler}"
    with open(os.path.join(out_dir, f"lda__{cfg_name}__{tag}.json"),
              "w") as f:
        json.dump(rec, f, indent=1)
    t = terms
    print(f"[ok] lda {cfg_name} {tag} {sampler}: "
          f"mem/dev={rec['memory']['total_gib_per_device']}GiB "
          f"c={t['compute_s']:.2e} m={t['memory_s']:.2e} "
          f"x={t['collective_s']:.2e} dom={t['dominant']}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=list(LDA_CONFIGS),
                    default="wiki-unigram-k5000")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--workers", type=int, default=64)
    ap.add_argument("--blocks-per-worker", type=int, default=1,
                    help="S: pipeline S*workers vocabulary blocks")
    ap.add_argument("--data-parallel", type=int, default=1,
                    help="D: replicate the block ring over D doc shards "
                         "(hybrid 2D grid; needs D*workers devices)")
    from repro.core.engine.rounds import available_samplers
    # registry-derived, no "auto": a dryrun lowers one named sampler, and
    # compile-only means interpret-mode Pallas needs no --force gate
    ap.add_argument("--sampler", default="batched",
                    choices=available_samplers())
    args = ap.parse_args()
    use_compile_cache()
    names = list(LDA_CONFIGS) if args.all else [args.config]
    failed = 0
    for name in names:
        try:
            run(name, args.workers, args.sampler,
                blocks_per_worker=args.blocks_per_worker,
                data_parallel=args.data_parallel)
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"[failed] lda {name}: {type(e).__name__}: {e}",
                  flush=True)
    if failed:
        sys.exit(f"{failed} of {len(names)} config(s) failed")


if __name__ == "__main__":
    main()
