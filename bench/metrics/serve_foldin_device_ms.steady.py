"""Device ms per dispatched batch in the fold-in programs
(``jit__fold_in_*``, ``core/infer.py``)."""
from harness.readers import foldin_ms_per_batch as read  # noqa: F401
