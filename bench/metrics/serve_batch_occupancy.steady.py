"""Percent of ``max_batch`` filled by real queries, averaged over the
batches the scheduler dispatched in the window (its ``batch_log``)."""
from harness.readers import batch_occupancy as read  # noqa: F401
