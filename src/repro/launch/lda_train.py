"""LDA training driver — the paper's own workload, end to end.

    PYTHONPATH=src python -m repro.launch.lda_train --docs 500 --vocab 2000 \
        --topics 50 --workers 8 --iters 30

Selects the model-parallel engine by default; ``--data-parallel D`` turns
it into the hybrid 2D (data × model) grid of DESIGN.md §8; ``--engine dp``
runs the Yahoo!LDA-style data-parallel baseline for comparison.

Out-of-core training (DESIGN.md §13): ``--corpus-dir`` points at a
sharded on-disk corpus (`python -m repro.data.stream`) and switches to
the streaming engine — memory bounded by one resident ``[Vb, K]`` block,
never the corpus or the full model.  ``--workdir`` holds the run's
persistent state; ``--checkpoint-every N`` snapshots it every N
iterations and ``--resume`` continues a killed run bit-exactly (the same
two flags also checkpoint/resume the in-memory mp engine, via
``ModelParallelLDA.save_checkpoint``/``resume``).  ``--snapshot-dir``
exports the final model as a sharded serving snapshot (one block file at
a time) that ``lda_infer --snapshot-dir`` serves row-restricted.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core.data_parallel import DataParallelLDA
from repro.core.infer import ModelSnapshot
from repro.core.likelihood import doc_completion_perplexity
from repro.core.metrics import topic_recovery_score
from repro.core.engine import ModelParallelLDA
from repro.data.corpus import split_corpus
from repro.data.synthetic import synthetic_corpus
from repro.launch.compile_cache import use_compile_cache
from repro.launch.samplers import (infer_sampler_choices,
                                   resolve_sampler_choice,
                                   resolve_store_choice, store_choices,
                                   train_sampler_choices)
from repro.train.checkpoint import save_checkpoint


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=["mp", "dp"], default="mp")
    ap.add_argument("--sampler", choices=train_sampler_choices(),
                    default="scan",
                    help="per-block sampler from the engine registry: "
                         "exact scan, word-frozen batched/pallas, O(1) "
                         "alias-table MH, or the hybrid sparse family "
                         "(DESIGN.md §9, §12); 'auto' picks the family "
                         "from the measured (K, doc-len) regime map and "
                         "the Pallas form of it on TPU")
    ap.add_argument("--force", action="store_true",
                    help="run an explicitly requested *_pallas sampler "
                         "in interpret mode off-TPU instead of refusing")
    ap.add_argument("--store", choices=store_choices(), default=None,
                    help="model CountStore layout (DESIGN.md §16): "
                         "'dense' keeps raw [Vb, K] blocks (default), "
                         "'tail' the hybrid dense-head/sparse-tail "
                         "record whose resident bytes track occupancy "
                         "instead of V*K; 'auto' picks tail exactly "
                         "where the regime map picks the sparse sampler "
                         "family. Draw-identical either way; on "
                         "--resume, keeps the run's store unless given")
    ap.add_argument("--table-lifetime",
                    choices=["auto", "round", "iteration"], default="auto",
                    help="MH proposal-table build schedule (DESIGN.md "
                         "§10): 'iteration' = traveling tables built once "
                         "per iteration (MH default), 'round' = rebuild "
                         "every round (the A/B baseline); 'auto' defers "
                         "to the engine default (mp engine, MH samplers)")
    ap.add_argument("--corpus-dir", default="",
                    help="sharded on-disk corpus directory (data/stream) "
                         "— switches to the out-of-core streaming engine "
                         "(requires --workdir)")
    ap.add_argument("--workdir", default="",
                    help="persistent run directory: the streaming "
                         "engine's state store, and the mp engine's "
                         "checkpoint home (engine_ckpt.npz)")
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                    help="checkpoint every N iterations into --workdir "
                         "(bit-exact resume via --resume)")
    ap.add_argument("--resume", action="store_true",
                    help="continue a killed run from the --workdir "
                         "checkpoint; draw-for-draw identical to a run "
                         "that never stopped")
    ap.add_argument("--docs", type=int, default=500)
    ap.add_argument("--vocab", type=int, default=2000)
    ap.add_argument("--topics", type=int, default=50)
    ap.add_argument("--doc-len", type=int, default=80)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--data-parallel", type=int, default=1,
                    help="D: hybrid grid — shard docs D*workers ways, "
                         "replicate the block ring D times (mp engine)")
    ap.add_argument("--blocks-per-worker", type=int, default=1,
                    help="S: pipeline S*workers vocabulary blocks "
                         "(mp engine)")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--beta", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--eval-every", type=int, default=1, metavar="N",
                    help="evaluate log likelihood every N iterations "
                         "(0 = never; evaluation gathers the full model, "
                         "so big streaming runs want 0)")
    ap.add_argument("--eval-holdout", type=int, default=0, metavar="N",
                    help="hold N docs out of training and report their "
                         "doc-completion perplexity each iteration "
                         "(fold-in on the first half of each held-out "
                         "doc, score the second half — DESIGN.md §11)")
    ap.add_argument("--holdout-sweeps", type=int, default=5,
                    help="fold-in Gibbs sweeps per holdout evaluation")
    ap.add_argument("--holdout-sampler", default="scan",
                    choices=infer_sampler_choices(),
                    help="fold-in sampler for the holdout eval ('scan' "
                         "avoids rebuilding alias tables every snapshot)")
    ap.add_argument("--snapshot-out", default="",
                    help="write the final frozen serving snapshot "
                         "(counts .npz consumed by lda_infer)")
    ap.add_argument("--snapshot-dir", default="",
                    help="export the final model as a SHARDED serving "
                         "snapshot directory, one block file at a time "
                         "(streaming engine; lda_infer --snapshot-dir)")
    ap.add_argument("--supervise", action="store_true",
                    help="run training under the crash-recovery "
                         "supervisor (DESIGN.md §15): on a crash, "
                         "quarantine corrupt/partial checkpoints into "
                         "workdir/quarantine/ and restart from the last "
                         "good one with bounded seeded backoff — the "
                         "recovered chain is bitwise the uninterrupted "
                         "one")
    ap.add_argument("--max-restarts", type=int, default=3, metavar="N",
                    help="restart budget under --supervise")
    args = ap.parse_args()

    if args.supervise:
        import sys

        from repro.launch.supervise import supervise_cli
        if not args.workdir:
            ap.error("--supervise needs --workdir (the checkpoint home "
                     "the supervisor quarantines and resumes from)")
        sys.exit(supervise_cli(sys.argv[1:], args.workdir,
                               max_restarts=args.max_restarts,
                               seed=args.seed))

    use_compile_cache()
    streaming = bool(args.corpus_dir) or (
        args.resume and args.workdir
        and os.path.exists(os.path.join(args.workdir, "run.json")))
    if streaming and not args.workdir:
        ap.error("--corpus-dir needs --workdir (the run's state store)")
    if streaming and args.engine != "mp":
        ap.error("--corpus-dir streams through the model-parallel "
                 "engine; --engine dp is in-memory only")
    if streaming and args.eval_holdout:
        ap.error("--eval-holdout needs the in-memory corpus; hold the "
                 "docs out when sharding the corpus instead")
    if (args.checkpoint_every or args.resume) and not args.workdir:
        ap.error("--checkpoint-every/--resume need --workdir")
    if args.checkpoint_every and args.engine == "dp":
        ap.error("--checkpoint-every supports the mp engines only")
    args.holdout_sampler = resolve_sampler_choice(args.holdout_sampler,
                                                  force=args.force)

    lifetime = (None if args.table_lifetime == "auto"
                else args.table_lifetime)
    phi = None
    holdout_docs = None
    mp_ckpt = (os.path.join(args.workdir, "engine_ckpt.npz")
               if args.workdir else "")

    if streaming:
        from repro.core.engine.streaming import StreamingLDA
        from repro.data.stream import ShardedCorpus
        if args.resume:
            lda = StreamingLDA.resume(args.workdir)
            if args.store is not None:
                # the run's geometry is known now, so 'auto' can consult
                # the regime map; set_store converts the on-disk block
                # files (the chain itself is store-invariant)
                new_store = resolve_store_choice(
                    args.store, num_topics=lda.num_topics,
                    max_doc_len=lda.max_doc_len)
                if new_store != lda.store_kind:
                    print(f"switching store {lda.store_kind!r} -> "
                          f"{new_store!r} (chain unchanged)")
                    lda.set_store(new_store)
            print(f"resumed streaming run at iteration "
                  f"{lda.iteration_count} (sampler={lda.sampler_mode}, "
                  f"store={lda.store_kind})")
        else:
            corpus = ShardedCorpus(args.corpus_dir)
            # the corpus exists now, so 'auto' can consult the measured
            # regime map (manifest carries max_doc_len — no shard reads)
            sampler = resolve_sampler_choice(
                args.sampler, force=args.force, num_topics=args.topics,
                max_doc_len=corpus.max_doc_len)
            store = resolve_store_choice(
                args.store or "dense", num_topics=args.topics,
                max_doc_len=corpus.max_doc_len)
            print(f"corpus: {corpus.num_tokens:,} tokens (sharded, "
                  f"{corpus.num_shards} shards), V={corpus.vocab_size:,}, "
                  f"K={args.topics}, sampler={sampler}, store={store}")
            lda = StreamingLDA(corpus, args.workdir, args.topics,
                               args.workers, alpha=args.alpha,
                               beta=args.beta, seed=args.seed,
                               sampler_mode=sampler,
                               blocks_per_worker=args.blocks_per_worker,
                               data_parallel=args.data_parallel,
                               table_lifetime=lifetime, store=store)
        note = lda.store_note()
        if note:
            # densification is never silent (DESIGN.md §16)
            print(f"NOTE: {note}")
        rep = lda.memory_report()
        print(f"resident block: {rep['resident_block_shape']} "
              f"({rep['resident_block_bytes'] / 2**20:.1f} MiB of "
              f"{rep['total_model_bytes'] / 2**20:.1f} MiB total model)")
        num_tokens = lda.num_tokens
    else:
        corpus, phi, _ = synthetic_corpus(args.docs, args.vocab,
                                          args.topics, args.doc_len,
                                          seed=args.seed)
        if args.eval_holdout:
            corpus, held = split_corpus(corpus, args.eval_holdout)
            holdout_docs = held.doc_words()
            print(f"holdout: {held.num_docs} docs / "
                  f"{held.num_tokens:,} tokens (doc-completion, "
                  f"{args.holdout_sweeps} fold-in sweeps, "
                  f"sampler={args.holdout_sampler})")
        args.sampler = resolve_sampler_choice(
            args.sampler, force=args.force, num_topics=args.topics,
            max_doc_len=int(corpus.doc_lengths().max(initial=1)))
        print(f"corpus: {corpus.num_tokens:,} tokens, V={args.vocab}, "
              f"K={args.topics}, model vars={args.vocab * args.topics:,}, "
              f"sampler={args.sampler}")
        max_len = int(corpus.doc_lengths().max(initial=1))
        if args.engine == "mp":
            if args.resume:
                store = (resolve_store_choice(args.store,
                                              num_topics=args.topics,
                                              max_doc_len=max_len)
                         if args.store is not None else None)
                lda = ModelParallelLDA.resume(corpus, mp_ckpt, store=store)
                print(f"resumed mp run at iteration {lda.iteration_count}"
                      f" (store={lda.store_kind})")
            else:
                store = resolve_store_choice(args.store or "dense",
                                             num_topics=args.topics,
                                             max_doc_len=max_len)
                lda = ModelParallelLDA(
                    corpus, args.topics, args.workers, alpha=args.alpha,
                    beta=args.beta, seed=args.seed,
                    sampler_mode=args.sampler,
                    blocks_per_worker=args.blocks_per_worker,
                    data_parallel=args.data_parallel,
                    table_lifetime=lifetime, store=store)
            print(f"table lifetime: {lda.table_lifetime}")
            note = lda.store_note()
            if note:
                # densification is never silent (DESIGN.md §16)
                print(f"NOTE: {note}")
        else:
            if args.store not in (None, "dense"):
                ap.error("--store supports the mp engines only; the dp "
                         "baseline replicates the dense model")
            lda = DataParallelLDA(corpus, args.topics, args.workers,
                                  alpha=args.alpha, beta=args.beta,
                                  seed=args.seed)
        num_tokens = corpus.num_tokens

    def take_snapshot():
        if hasattr(lda, "snapshot"):
            return lda.snapshot()
        state = lda.gather_counts()   # dp baseline: build from the dump
        return ModelSnapshot.from_counts(np.asarray(state.ckt),
                                         np.asarray(state.ck),
                                         args.alpha, args.beta)

    def checkpoint():
        if streaming:
            lda.save_checkpoint()
        else:
            lda.save_checkpoint(mp_ckpt)

    history = []
    t0 = time.time()
    for it in range(lda.iteration_count + 1, args.iters + 1):
        t_it = time.perf_counter()
        lda.step()
        iter_s = time.perf_counter() - t_it   # sampling only, no eval
        rec = {"iteration": it, "iter_s": round(iter_s, 4),
               "tokens_per_s": round(num_tokens / iter_s, 1),
               "elapsed_s": round(time.time() - t0, 2)}
        lstr = ""
        if args.eval_every and it % args.eval_every == 0:
            ll = lda.log_likelihood()
            rec["log_likelihood"] = ll
            lstr = f"LL {ll:,.0f}  "
        if not streaming:
            if args.engine == "mp":
                rec["delta_error"] = lda.delta_error()
            else:
                rec["staleness_error"] = lda.model_error()
        hstr = ""
        if holdout_docs is not None:
            ppl = doc_completion_perplexity(
                take_snapshot(), holdout_docs,
                num_sweeps=args.holdout_sweeps,
                sampler=args.holdout_sampler, seed=args.seed + it)
            rec["holdout_perplexity"] = ppl["perplexity"]
            hstr = f"ppl {ppl['perplexity']:,.1f}  "
        history.append(rec)
        if args.checkpoint_every and it % args.checkpoint_every == 0:
            checkpoint()
            rec["checkpointed"] = True
        if it % max(args.iters // 10, 1) == 0 or it == 1:
            err = rec.get("delta_error", rec.get("staleness_error"))
            extra = f"Δ={err:.5f}" if err is not None else ""
            print(f"iter {it:4d}  {lstr}{hstr}{extra}  "
                  f"{rec['iter_s']:.3f}s/iter "
                  f"{rec['tokens_per_s']:,.0f} tok/s  "
                  f"[{rec['elapsed_s']}s]", flush=True)
    # steady-state throughput: median over post-warmup iterations (the
    # first pays jit compilation)
    if len(history) > 1:
        import statistics
        med = statistics.median(r["tokens_per_s"] for r in history[1:])
        print(f"median throughput: {med:,.0f} tokens/s")
    score = None
    if phi is not None:
        score = topic_recovery_score(np.asarray(lda.gather_counts().ckt),
                                     phi)
        print(f"topic recovery score: {score:.3f}")
    if args.ckpt:
        state = lda.gather_counts()
        save_checkpoint(args.ckpt, {"ckt": state.ckt, "cdk": state.cdk,
                                    "ck": state.ck}, step=args.iters)
        print(f"saved model to {args.ckpt}")
    if args.snapshot_out:
        take_snapshot().save(args.snapshot_out)
        print(f"saved serving snapshot to {args.snapshot_out}")
    if args.snapshot_dir:
        if not streaming:
            ap.error("--snapshot-dir is the streaming engine's sharded "
                     "export; use --snapshot-out for in-memory engines")
        lda.save_snapshot_sharded(args.snapshot_dir)
        print(f"saved sharded serving snapshot to {args.snapshot_dir}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"history": history, "recovery": score}, f, indent=1)


if __name__ == "__main__":
    main()
