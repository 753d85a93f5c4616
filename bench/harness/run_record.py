"""What a runner hands back: the facts the metric files read, and the
inputs of the checks that decide ``correct``."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List


@dataclasses.dataclass
class Run:
    setup_s: float
    window_s: float
    chips: int
    counts: Dict[str, float]
    attempted: int
    failed: int
    device: Dict[str, Any]
    check_input: Any
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    batch_log: List[dict] = dataclasses.field(default_factory=list)
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)
