"""Core of the reproduction: model-parallel collapsed Gibbs LDA."""
from repro.core.counts import CountState, build_counts, check_invariants
from repro.core.data_parallel import DataParallelLDA
from repro.core.engine import EngineLayout
from repro.core.likelihood import log_likelihood
from repro.core.metrics import delta_error, topic_recovery_score
from repro.core.engine import ModelParallelLDA, MPState
from repro.core.schedule import partition_vocab, rotation_permutation

__all__ = [
    "CountState", "build_counts", "check_invariants", "DataParallelLDA",
    "EngineLayout", "log_likelihood", "delta_error", "topic_recovery_score",
    "ModelParallelLDA", "MPState", "partition_vocab", "rotation_permutation",
]
