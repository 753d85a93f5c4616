#!/usr/bin/env python3
"""Read the checks' numbers for the plain reference put in the program's
place: at the configuration's own precision (must pass), one precision
lower (the control, must fail), and with the faults a cell can have
planted in it (must fail).  These readings set the upper end of each
check's limit (PERF.md); the benchmark's own runs never run this.

    python3 bench/tools/controls.py --workload train-pubmed-k1000 --seeds 1,2,3
    python3 bench/tools/controls.py --workload serve-pubmed-k1000-steady --seeds 1,2,3
    ... --rehearse        # CPU, the configuration's rehearsal sizes

Training variants (one round of the reference sampler from a warmed
state): ``f32``; ``bf16`` (counts and arithmetic in bfloat16);
``bf16_arith`` (arithmetic in bfloat16, counts in int32: which the
checks cannot tell from f32, PERF.md);
``unchanged`` (the round returns its state); ``half`` (half the tokens
left out of the round); ``token`` (one token's topic altered after the
round, its counts not).  Serving variants (answers for the cell's trace):
``f64``; ``f32`` (another summation order, must be explained);
``bf16``; ``answer`` (one answer altered)."""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from harness import common, gen  # noqa: E402
from reference import lda as ref  # noqa: E402

TRAIN_VARIANTS = ("f32", "bf16", "bf16_arith", "unchanged", "half",
                  "token")
SERVE_VARIANTS = ("f64", "f32", "bf16", "answer")


def run_checks(cfg, outcome):
    rows = []
    for name in cfg["checks"]:
        rows += common.load_module("checks", name).check(outcome)
    return {n: {"value": v, "limit": lim} for n, v, lim in rows}, all(
        v <= lim for _, v, lim in rows)


def train_rows(jax, cfg, mix, seed, variants):
    import jax.numpy as jnp
    from harness.train import TrainOutcome
    v, k, d = cfg["vocab_size"], cfg["num_topics"], cfg["num_docs"]
    doc, word = gen.corpus(seed, d, v, mix)
    z0 = np.random.default_rng([seed, 5]).integers(0, k, word.shape[0])
    z0 = z0.astype(np.int32)

    def sampler(z, dtype, stream, count_dtype=None):
        return ref.mh_round(jax, gen.jax_key(jax, seed, stream), word, doc,
                            z, v, d, cfg["alpha"], cfg["beta"],
                            cfg["mh_cycles"], dtype, k, count_dtype)

    z_before = sampler(z0, jnp.float32, 3)[0]
    for var in variants:
        z_after, ckt, cdk, ck = sampler(
            z_before, jnp.bfloat16 if var.startswith("bf16") else
            jnp.float32, 4, jnp.int32 if var == "bf16_arith" else None)
        if var in ("unchanged", "half"):
            if var == "unchanged":
                z_after = z_before.copy()
            else:
                keep = np.random.default_rng([seed, 6]).random(
                    z_after.shape[0]) < 0.5
                z_after = np.where(keep, z_before, z_after)
            ckt, cdk, ck = ref.recount(word, doc, z_after, v, d, k)
        elif var == "token":
            z_after = z_after.copy()
            z_after[0] = (z_after[0] + 1) % k
        out = TrainOutcome(
            word=word, doc=doc, vocab_size=v, num_docs=d, num_topics=k,
            alpha=cfg["alpha"], beta=cfg["beta"], cycles=cfg["mh_cycles"],
            seed=seed, z_before=z_before, z_after=z_after,
            ckt=np.asarray(ckt, np.float64), cdk=np.asarray(cdk, np.float64),
            ck=np.asarray(ck, np.float64),
            ck_synced=np.asarray(ck, np.float64),
            ck_local=np.asarray(ck, np.float64)[None, :])
        yield var, out


def serve_rows(jax, cfg, mix, seed, seconds, variants):
    from harness.serve import ServeOutcome
    v, k = cfg["vocab_size"], cfg["num_topics"]
    ckt = gen.model_counts(jax, seed, v, k, cfg["counts_law"])
    ck = ckt.sum(axis=0, dtype=np.int64).astype(np.int32)
    alpha32 = np.full(k, cfg["alpha"], np.float32)
    fp = ref.model_fingerprint(ckt, ck, alpha32, cfg["beta"])
    trace = gen.requests(seed, seconds, v, mix)
    prec = {"f64": "f64", "f32": "f32", "bf16": "bf16", "answer": "f64"}
    draws = [ref.request_draws(seed, fp, toks, k, cfg["sweeps"])
             for _, toks in trace]
    for var in variants:
        answers = ref.foldin_answers(
            [ref.word_term(ckt[canon], ck, cfg["beta"], cfg["beta"] * v,
                           prec[var]) for canon, _, _ in draws],
            alpha32, [z0 for _, z0, _ in draws], [u for _, _, u in draws],
            prec[var])
        if var == "answer":
            c = ref.counts_of_theta(answers[0], trace[0][1].size, alpha32)
            top = int(np.argmax(c))
            c[top] -= 1
            c[(top + 1) % k] += 1
            answers[0] = ref.theta(c, alpha32)
        yield var, ServeOutcome(ckt=ckt, ck=ck, alpha=cfg["alpha"],
                                beta=cfg["beta"], seed=seed,
                                sweeps=cfg["sweeps"],
                                tokens=[t for _, t in trace],
                                answers=answers)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.argv = sys.argv[:1]
    import run as bench_run
    bench, cell, cfg, mix = bench_run.load_cell(args.workload)
    if args.rehearse:
        cfg, mix = bench_run.rehearsal(cfg, mix)
    jax = common.start_jax(args.rehearse, cell["chips"])
    seconds = args.seconds or bench["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        if cfg["kind"] == "train":
            variants = args.variants.split(",") if args.variants \
                else TRAIN_VARIANTS
            rows = train_rows(jax, cfg, mix, seed, variants)
        else:
            variants = args.variants.split(",") if args.variants \
                else SERVE_VARIANTS
            rows = serve_rows(jax, cfg, mix, seed, seconds, variants)
        for var, outcome in rows:
            checks, correct = run_checks(cfg, outcome)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": var, "correct": correct,
                              "checks": checks}), flush=True)


if __name__ == "__main__":
    main()
