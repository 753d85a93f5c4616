"""Percent of the ring's window in which no op ran, averaged over the
cell's chips."""
from harness.readers import idle_share as read  # noqa: F401
