"""Serving driver: answer topic-inference queries from a frozen snapshot.

    # serve a snapshot exported by `lda_train --snapshot-out`
    PYTHONPATH=src python -m repro.launch.lda_infer \
        --snapshot /tmp/snap.npz --queries 16 --query-len 32 --sampler mh

    # serve a SHARDED snapshot (lda_train --snapshot-dir) out-of-core:
    # only the [U, K] rows the batch's distinct words hit are loaded
    PYTHONPATH=src python -m repro.launch.lda_infer \
        --snapshot-dir /tmp/snapdir --queries 16 --query-len 32

    # self-contained demo: train a tiny model, hold docs out, serve them
    PYTHONPATH=src python -m repro.launch.lda_infer \
        --docs 200 --vocab 500 --topics 20 --train-iters 10 --queries 16

Loads (or trains) a model, stands up a :class:`TopicInferenceServer`,
infers ``θ̂`` for a batch of unseen documents, and reports the batch
latency plus the doc-completion perplexity of the queries.  Exits
non-zero if the perplexity is not finite — the CI smoke contract
(`scripts/ci.sh` passes 5 and 7).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types

import numpy as np

from repro.core.infer import (load_sharded_snapshot_meta, load_snapshot,
                              load_snapshot_rows)
from repro.data.corpus import load_corpus, split_corpus
from repro.launch.compile_cache import use_compile_cache
from repro.launch.samplers import (infer_sampler_choices,
                                   resolve_sampler_choice)
from repro.serve.topic_infer import TopicInferenceServer


def _queries_from_args(args, snap):
    """Query docs: a saved corpus (`--query-corpus`), else random words —
    uniform queries are the worst case for the model, but perplexity is
    still finite because ``φ̂`` is β-smoothed everywhere."""
    if args.query_corpus:
        corpus = load_corpus(args.query_corpus)
        if corpus.vocab_size > snap.vocab_size:
            raise SystemExit(
                f"query corpus vocab ({corpus.vocab_size}) exceeds the "
                f"snapshot's ({snap.vocab_size})")
        return corpus.doc_words()[:args.queries]
    rng = np.random.default_rng(args.seed + 1)
    return [rng.integers(0, snap.vocab_size,
                         size=args.query_len).astype(np.int32)
            for _ in range(args.queries)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--snapshot", default="",
                    help="frozen snapshot (.npz from lda_train "
                         "--snapshot-out); empty = self-train a tiny "
                         "model and query its held-out docs")
    ap.add_argument("--snapshot-dir", default="",
                    help="sharded snapshot directory (lda_train "
                         "--snapshot-dir): loads only the count rows the "
                         "query batch touches — the full [V, K] model "
                         "never enters memory (DESIGN.md §13)")
    ap.add_argument("--query-corpus", default="",
                    help="saved corpus whose docs become the queries "
                         "(with --snapshot)")
    ap.add_argument("--sampler", choices=infer_sampler_choices(),
                    default="mh",
                    help="fold-in sampler (DESIGN.md §11–§12): exact "
                         "scan, the O(1) alias-table MH pair, or the "
                         "hybrid sparse family; 'auto' picks per "
                         "platform")
    ap.add_argument("--force", action="store_true",
                    help="run an explicitly requested *_pallas sampler "
                         "in interpret mode off-TPU instead of refusing")
    ap.add_argument("--sweeps", type=int, default=5)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--query-len", type=int, default=32)
    ap.add_argument("--top", type=int, default=3,
                    help="top topics to print per query")
    # self-train flags (ignored with --snapshot)
    ap.add_argument("--docs", type=int, default=120)
    ap.add_argument("--vocab", type=int, default=300)
    ap.add_argument("--topics", type=int, default=12)
    ap.add_argument("--doc-len", type=int, default=40)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--train-iters", type=int, default=5)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--beta", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    use_compile_cache()
    args.sampler = resolve_sampler_choice(args.sampler, force=args.force)

    if args.snapshot and args.snapshot_dir:
        ap.error("--snapshot and --snapshot-dir are mutually exclusive")
    if args.snapshot_dir:
        meta = load_sharded_snapshot_meta(args.snapshot_dir)
        # queries live in the TRUE vocab id space; the row-restricted
        # view remaps them after the batch's word set is known
        queries = _queries_from_args(
            args, types.SimpleNamespace(vocab_size=meta["vocab_size"]))
        lens = [len(d) for d in queries]
        flat = np.concatenate([np.asarray(d, np.int32) for d in queries])
        snap, remapped = load_snapshot_rows(args.snapshot_dir, flat)
        queries = np.split(remapped, np.cumsum(lens)[:-1])
        print(f"sharded snapshot: V={meta['vocab_size']:,} "
              f"K={meta['num_topics']} ({meta['num_blocks']} block "
              f"files, store={meta['store']}); batch touches "
              f"{snap.vocab_size:,} distinct "
              f"words -> resident rows [{snap.vocab_size}, "
              f"{snap.num_topics}] "
              f"({snap.ckt.nbytes / 2**20:.2f} MiB of "
              f"{meta['vocab_size'] * meta['num_topics'] * 4 / 2**20:.1f}"
              f" MiB full model)")
        if meta["store"] != "dense":
            # densification is never silent (DESIGN.md §16): only the
            # touched rows decode to dense — never the full model
            print(f"NOTE: store={meta['store']!r} block records decode "
                  f"their touched rows to dense [U, K] for serving")
    elif args.snapshot:
        snap = load_snapshot(args.snapshot)
        print(f"snapshot: V={snap.vocab_size} K={snap.num_topics} "
              f"({snap.ck.sum():,} training tokens)")
        queries = _queries_from_args(args, snap)
    else:
        from repro.core.engine import ModelParallelLDA
        from repro.data.synthetic import synthetic_corpus
        corpus, _, _ = synthetic_corpus(args.docs, args.vocab, args.topics,
                                        args.doc_len, seed=args.seed)
        corpus, held = split_corpus(corpus, args.queries)
        print(f"self-train: {corpus.num_tokens:,} tokens, "
              f"{args.train_iters} iters; querying the {held.num_docs} "
              f"held-out docs")
        lda = ModelParallelLDA(corpus, args.topics, args.workers,
                               alpha=args.alpha, beta=args.beta,
                               seed=args.seed)
        lda.run(args.train_iters)
        snap = lda.snapshot()
        queries = held.doc_words()

    server = TopicInferenceServer(snap, sampler=args.sampler,
                                  num_sweeps=args.sweeps, seed=args.seed)
    t0 = time.perf_counter()
    theta = server.infer(queries)          # includes jit compile
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    theta = server.infer(queries)
    warm_s = time.perf_counter() - t0
    qb, tb = server.bucket_shape(queries)
    print(f"batch of {len(queries)} queries -> bucket ({qb}, {tb}); "
          f"cold {cold_s * 1e3:.1f} ms, warm {warm_s * 1e3:.1f} ms "
          f"({len(queries) / warm_s:,.1f} queries/s)")
    for i, th in enumerate(theta[:min(len(queries), 4)]):
        top = np.argsort(th)[::-1][:args.top]
        desc = ", ".join(f"k{t}:{th[t]:.2f}" for t in top)
        print(f"  query {i}: {desc}")

    ppl = server.perplexity(queries)
    true_v = snap.true_vocab_size or snap.vocab_size
    print(f"doc-completion perplexity: {ppl['perplexity']:,.2f} over "
          f"{ppl['tokens_scored']} scored tokens "
          f"(V = {true_v} is the uninformative ceiling)")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"perplexity": ppl, "warm_batch_s": warm_s,
                       "cold_batch_s": cold_s,
                       "bucket": [qb, tb],
                       "theta": np.asarray(theta).tolist()}, f, indent=1)
    if not np.isfinite(ppl["perplexity"]):
        sys.exit("non-finite held-out perplexity — serving smoke FAILED")


if __name__ == "__main__":
    main()
