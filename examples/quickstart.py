"""Quickstart: train a model-parallel LDA on a tiny synthetic corpus and
inspect the learned topics.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.metrics import top_words, topic_recovery_score
from repro.core.engine import ModelParallelLDA
from repro.data.synthetic import synthetic_corpus

# 1. Data: a corpus with 8 planted topics (each owning a word band).
corpus, true_phi, _ = synthetic_corpus(
    num_docs=200, vocab_size=400, num_topics=8, doc_len=60, seed=0)
print(f"corpus: {corpus.num_tokens:,} tokens, {corpus.num_docs} docs, "
      f"V={corpus.vocab_size}")

# 2. Model-parallel LDA: 4 workers, each holding 1/4 of the word-topic
#    table; blocks rotate each round (the paper's Algorithm 1+2).
lda = ModelParallelLDA(corpus, num_topics=8, num_workers=4,
                       alpha=0.1, beta=0.01, seed=1)
print(f"word blocks: {lda.partition.num_blocks} × {lda.partition.block_size}"
      f" words; per-worker model = {np.asarray(lda.state.ckt)[0].nbytes:,}"
      " bytes")

# 3. Run 20 iterations, watching likelihood ascend and the Fig-3 error
#    stay tiny.
for it in range(1, 21):
    lda.step()
    if it % 5 == 0 or it == 1:
        print(f"iter {it:3d}  log-likelihood {lda.log_likelihood():,.0f}  "
              f"Δ-error {lda.delta_error():.5f}")

# 4. Inspect: top words per topic + recovery of the planted structure.
ckt = np.asarray(lda.gather_counts().ckt)
for k in range(8):
    print(f"topic {k}: words {top_words(ckt, k, 8).tolist()}")
score = topic_recovery_score(ckt, true_phi)
print(f"topic recovery vs planted topics: {score:.3f} (1.0 = perfect)")
assert score > 0.5

# 5. Model capacity beyond worker RAM: pipeline S blocks per worker —
#    the resident block shrinks S-fold at the same worker count while
#    inference stays exact (DESIGN.md §3).
deep = ModelParallelLDA(corpus, num_topics=8, num_workers=4,
                        alpha=0.1, beta=0.01, seed=1, blocks_per_worker=4)
rep = deep.memory_report()
print(f"\nblocks_per_worker=4: {rep['num_blocks']} blocks, resident block "
      f"{rep['resident_block_shape']} = {rep['resident_block_bytes']:,} B "
      f"of a {rep['total_model_bytes']:,} B model")
deep.run(5)
print(f"pipelined engine log-likelihood after 5 iters: "
      f"{deep.log_likelihood():,.0f}")
