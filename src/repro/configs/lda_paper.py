"""The paper's own workload configs (§5 Experiments).

Not an ``ArchConfig`` — LDA is not a transformer — but registered here so
the launcher and dry-run can select the paper's exact problem
sizes by name.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class LDAConfig:
    name: str
    vocab_size: int
    num_topics: int
    num_docs: int
    num_tokens: int
    alpha: float = 0.1
    beta: float = 0.01

    @property
    def model_variables(self) -> int:
        return self.vocab_size * self.num_topics


# Table-1 / §5 dataset scales
PUBMED_1K = LDAConfig("pubmed-k1000", 141_043, 1_000, 8_200_000, 737_900_000)
PUBMED_5K = LDAConfig("pubmed-k5000", 141_043, 5_000, 8_200_000, 737_900_000)
WIKI_UNIGRAM_5K = LDAConfig("wiki-unigram-k5000", 2_500_000, 5_000,
                            3_900_000, 179_000_000)
WIKI_UNIGRAM_10K = LDAConfig("wiki-unigram-k10000", 2_500_000, 10_000,
                             3_900_000, 179_000_000)
WIKI_BIGRAM_5K = LDAConfig("wiki-bigram-k5000", 21_800_000, 5_000,
                           3_900_000, 79_000_000)
# the 218-billion-variable flagship run (Table 1, rightmost column)
WIKI_BIGRAM_10K = LDAConfig("wiki-bigram-k10000", 21_800_000, 10_000,
                            3_900_000, 79_000_000)

LDA_CONFIGS = {c.name: c for c in [
    PUBMED_1K, PUBMED_5K, WIKI_UNIGRAM_5K, WIKI_UNIGRAM_10K,
    WIKI_BIGRAM_5K, WIKI_BIGRAM_10K]}
