"""Percent of the serving window in which no op ran on the device."""
from harness.readers import idle_share as read  # noqa: F401
