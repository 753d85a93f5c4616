#!/usr/bin/env bash
# Tier-1 verification entry point (ROADMAP.md): run the full test suite
# with src/ on PYTHONPATH.  Extra pytest args pass through, e.g.
#   scripts/ci.sh -m "not slow"
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Pass 1: full suite.  conftest.py fakes 4 host devices when XLA_FLAGS
# carries no explicit count, so shard_map tests run in-process here too.
python -m pytest -x -q "$@"

# Pass 2: the engine equivalence harness under an EXPLICIT 4-device host —
# guards the hybrid 2D (data, model) shard_map path even in environments
# whose ambient XLA_FLAGS would otherwise pin a different device count.
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python -m pytest -x -q tests/test_engine_2d.py tests/test_engine_blocks.py

# Pass 3: seeded statistical stage — the slow-marked MH-vs-exact chain
# equivalence bounds (chi-square/tolerance, DESIGN.md §9) with the hash
# seed and the 4-device host pinned, so the declared flaky-tolerance
# bounds are exercised deterministically rather than sampled.  Only the
# `slow` marker runs here: pass 1 already covers the fast structural
# tests, and all chain randomness flows from numpy Generator(seed)
# streams pinned inside the tests.
PYTHONHASHSEED=0 \
    XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python -m pytest -x -q -m slow tests/test_mh_stats.py

# Pass 4: train -> snapshot -> serve smoke (DESIGN.md §11).  A 2-iter
# training run exports a frozen snapshot (reporting held-out
# doc-completion perplexity along the way) and lda_infer serves a query
# batch from it (exits non-zero on non-finite perplexity) — the full
# query path from CLI to fold-in kernel exercised on every CI run.
SNAP_DIR="$(mktemp -d)"
python -m repro.launch.lda_infer --queries 6 --query-len 16 --sweeps 3 \
    --docs 48 --vocab 96 --topics 8 --train-iters 2
python -m repro.launch.lda_train --docs 48 --vocab 96 --topics 8 \
    --workers 2 --iters 2 --eval-holdout 8 --snapshot-out "$SNAP_DIR/snap.npz"
python -m repro.launch.lda_infer --snapshot "$SNAP_DIR/snap.npz" \
    --queries 8 --query-len 24 --sweeps 3
rm -rf "$SNAP_DIR"

# Pass 5: hybrid sparse family smoke (DESIGN.md §12) — pinned-seed
# 4-device hybrid-grid training with the sparse sampler (train ->
# snapshot -> sparse fold-in serve through the CLI).  Guards the whole
# §12 surface: registry resolution with static sampler args, the 2D
# shard_map path, snapshot sparse state, and the serving alias.
SPARSE_DIR="$(mktemp -d)"
PYTHONHASHSEED=0 XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python -m repro.launch.lda_train --docs 48 --vocab 96 --topics 8 \
    --workers 2 --data-parallel 2 --iters 2 --seed 3 --sampler sparse \
    --eval-holdout 8 --holdout-sampler sparse \
    --snapshot-out "$SPARSE_DIR/snap.npz"
PYTHONHASHSEED=0 \
    python -m repro.launch.lda_infer --snapshot "$SPARSE_DIR/snap.npz" \
    --sampler sparse --queries 8 --query-len 24 --sweeps 3
rm -rf "$SPARSE_DIR"

# Pass 6: out-of-core streaming + bit-exact resume smoke (DESIGN.md §13).
# Shard a corpus to disk, train the streaming engine 2 iterations with
# per-iteration checkpoints, then "crash" and resume the run to 4
# iterations from the workdir alone (no corpus flags — geometry, sampler
# and rng all come from the checkpoint), export a SHARDED serving
# snapshot, and serve it row-restricted through lda_infer
# --snapshot-dir.  --sampler auto also exercises the measured regime
# map's (K, doc-len) lookup on a real manifest.
STREAM_DIR="$(mktemp -d)"
python -m repro.data.stream --out "$STREAM_DIR/corpus" --zipf 1.1 \
    --docs 64 --vocab 128 --doc-len 24 --shards 4 --seed 11
python -m repro.launch.lda_train --corpus-dir "$STREAM_DIR/corpus" \
    --workdir "$STREAM_DIR/run" --topics 8 --workers 2 \
    --blocks-per-worker 2 --iters 2 --sampler auto --checkpoint-every 1
python -m repro.launch.lda_train --workdir "$STREAM_DIR/run" --resume \
    --iters 4 --checkpoint-every 2 --snapshot-dir "$STREAM_DIR/snap"
python -m repro.launch.lda_infer --snapshot-dir "$STREAM_DIR/snap" \
    --queries 8 --query-len 16 --sweeps 3 --sampler scan
rm -rf "$STREAM_DIR"

# Pass 7: serving-scheduler traffic-replay smoke (DESIGN.md §14).  Shard
# a corpus, train the streaming engine twice to two SHARDED snapshots
# (earlier + later iterations of one run), then replay a seeded
# open-loop Poisson trace through lda_serve with a mid-replay hot-swap
# between them — exits non-zero if any admitted request is dropped, p99
# is non-finite, or the post-swap epoch never serves.  Both snapshot
# directories are row-restricted with the SAME word set, so the swap
# stays a pointer flip.
SERVE_DIR="$(mktemp -d)"
python -m repro.data.stream --out "$SERVE_DIR/corpus" --zipf 1.1 \
    --docs 64 --vocab 128 --doc-len 24 --shards 4 --seed 11
python -m repro.launch.lda_train --corpus-dir "$SERVE_DIR/corpus" \
    --workdir "$SERVE_DIR/run" --topics 8 --workers 2 --iters 2 \
    --checkpoint-every 2 --snapshot-dir "$SERVE_DIR/snapA"
python -m repro.launch.lda_train --workdir "$SERVE_DIR/run" --resume \
    --iters 4 --checkpoint-every 2 --snapshot-dir "$SERVE_DIR/snapB"
python -m repro.launch.lda_serve --snapshot-dir "$SERVE_DIR/snapA" \
    --swap-snapshot-dir "$SERVE_DIR/snapB" --swap-after 12 \
    --requests 32 --rate 400 --max-len 16 --sweeps 3 --seed 0
rm -rf "$SERVE_DIR"

# Pass 8: fault-injection + crash-recovery smoke (DESIGN.md §15).  A
# reference streaming run trains uninterrupted to 4 iterations; a second
# run gets a scripted crash (REPRO_FAULT_PLAN kills it at the start of
# iteration 3, the in-process model of SIGKILL) under
# `lda_train --supervise`, which quarantines debris and auto-resumes
# from the last good checkpoint.  The two workdirs must then be
# BITWISE equal: counts, every assignment, and the rng bit-generator
# state — recovery is invisible, not approximate.  Then the scheduler
# rides through a dead replica: lda_serve with replica 0 scripted to
# fail every dispatch must answer 100% of admitted queries (it exits
# non-zero on any drop) and prints the breaker/fault counters.
FT_DIR="$(mktemp -d)"
python -m repro.data.stream --out "$FT_DIR/corpus" --zipf 1.1 \
    --docs 64 --vocab 128 --doc-len 24 --shards 4 --seed 11
python -m repro.launch.lda_train --corpus-dir "$FT_DIR/corpus" \
    --workdir "$FT_DIR/run_ref" --topics 8 --workers 2 --iters 4 \
    --checkpoint-every 1 --sampler scan
REPRO_FAULT_PLAN='{"format":"fault-plan-v1","seed":0,"specs":[{"kind":"crash","point":"step","match":"iter:2,","nth":1,"arg":0.0}]}' \
    python -m repro.launch.lda_train --corpus-dir "$FT_DIR/corpus" \
    --workdir "$FT_DIR/run_crash" --topics 8 --workers 2 --iters 4 \
    --checkpoint-every 1 --sampler scan --supervise --max-restarts 2
python - "$FT_DIR/run_ref" "$FT_DIR/run_crash" << 'PYEOF'
import sys
import numpy as np
from repro.core.engine.streaming import StreamingLDA
ref = StreamingLDA.resume(sys.argv[1])
rec = StreamingLDA.resume(sys.argv[2])
assert ref.iteration_count == rec.iteration_count == 4, \
    (ref.iteration_count, rec.iteration_count)
sa, sb = ref.gather_counts(), rec.gather_counts()
for name in ("cdk", "ckt", "ck"):
    np.testing.assert_array_equal(np.asarray(getattr(sa, name)),
                                  np.asarray(getattr(sb, name)),
                                  err_msg=f"{name} diverged")
np.testing.assert_array_equal(ref.assignments(), rec.assignments(),
                              err_msg="assignments diverged")
assert ref._rng.bit_generator.state == rec._rng.bit_generator.state, \
    "rng state diverged"
print("bitwise: crashed+supervised chain == uninterrupted chain")
PYEOF
python -m repro.launch.lda_train --workdir "$FT_DIR/run_crash" --resume \
    --iters 4 --snapshot-dir "$FT_DIR/snap"
python -m repro.launch.lda_serve --snapshot-dir "$FT_DIR/snap" \
    --replicas 2 --inject-replica-fail 0 --breaker-cooldown 0.05 \
    --requests 32 --rate 400 --max-len 16 --sweeps 3 --seed 0
rm -rf "$FT_DIR"

# Pass 9: pluggable CountStore smoke (DESIGN.md §16).  Two streaming
# pipelines over the same Zipf corpus — store=dense vs store=tail (K=64
# so wcap=32 head rows actually occur) — each: train 2 iters with the
# sparse sampler, checkpoint, resume to 4 iters, export a sharded
# snapshot, serve it row-restricted through lda_infer.  The two chains
# must be BITWISE equal (counts, assignments, rng state) and the tail
# run's block files must really be store-v2 .npz records — the
# store-invariance contract exercised end to end through the CLI.
CS_DIR="$(mktemp -d)"
python -m repro.data.stream --out "$CS_DIR/corpus" --zipf 1.1 \
    --docs 64 --vocab 128 --doc-len 24 --shards 4 --seed 11
for S in dense tail; do
    python -m repro.launch.lda_train --corpus-dir "$CS_DIR/corpus" \
        --workdir "$CS_DIR/run_$S" --topics 64 --workers 2 \
        --blocks-per-worker 2 --iters 2 --sampler sparse --store "$S" \
        --eval-every 0 --checkpoint-every 1
    python -m repro.launch.lda_train --workdir "$CS_DIR/run_$S" --resume \
        --iters 4 --eval-every 0 --checkpoint-every 2 \
        --snapshot-dir "$CS_DIR/snap_$S"
    python -m repro.launch.lda_infer --snapshot-dir "$CS_DIR/snap_$S" \
        --queries 8 --query-len 16 --sweeps 3 --sampler scan
done
python - "$CS_DIR" << 'PYEOF'
import glob, json, os, sys
import numpy as np
from repro.core.engine.streaming import StreamingLDA
root = sys.argv[1]
a = StreamingLDA.resume(os.path.join(root, "run_dense"))
b = StreamingLDA.resume(os.path.join(root, "run_tail"))
assert (a.store_kind, b.store_kind) == ("dense", "tail")
assert glob.glob(os.path.join(root, "run_tail", "state", "blocks",
                              "*.npz")), "tail run wrote no .npz records"
assert not glob.glob(os.path.join(root, "run_tail", "state", "blocks",
                                  "*.npy")), "stale dense block files"
sa, sb = a.gather_counts(), b.gather_counts()
for name in ("cdk", "ckt", "ck"):
    np.testing.assert_array_equal(np.asarray(getattr(sa, name)),
                                  np.asarray(getattr(sb, name)),
                                  err_msg=f"{name} diverged")
np.testing.assert_array_equal(a.assignments(), b.assignments(),
                              err_msg="assignments diverged")
assert a._rng.bit_generator.state == b._rng.bit_generator.state, \
    "rng state diverged"
m1 = json.load(open(os.path.join(root, "snap_dense", "meta.json")))
m2 = json.load(open(os.path.join(root, "snap_tail", "meta.json")))
assert m1["format"] == "sharded-snapshot-v1" and m1["store"] == "dense"
assert m2["format"] == "sharded-snapshot-v2" and m2["store"] == "tail"
print("bitwise: store=tail pipeline == store=dense pipeline")
PYEOF
rm -rf "$CS_DIR"
