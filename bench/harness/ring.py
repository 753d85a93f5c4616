"""Ring cells: the training runner (``harness/train.py``) on the
``S·M``-block ring over the cell's chips, with what the ring's checks and
readers need besides.

The check input gains the ring's geometry (``M``, ``S``) and the rule by
which documents are dealt to workers, for ``checks/ring_transition.py``.
The counts gain the engine's per-iteration counters
(``ModelParallelLDA.counters``: slots sampled, real tokens, bytes handed
on through the ring) times the window's iterations, and the geometry the
rotation's roofline is computed from.  A program without the counters
leaves them out, and the padding share then finds nothing to read.

The notes give each chip's peak memory at the window's end and the fewest
devices any layout or state array spanned right after the engine was
built (``M`` when every array was split over the mesh before the first
step).  The peaks are read before the checks' inputs are gathered: that
gather puts the whole model on the default device, and is no part of
the ring."""
from __future__ import annotations

import types

from harness import train
from harness.run_record import Run


def run(jax, cfg: dict, mix: dict, seed: int, seconds: float,
        chips: int, trace_dir=None) -> Run:
    from repro.core import engine

    real = engine.ModelParallelLDA
    built = {}

    class Counted(real):
        """The engine as built, with its counters and placement noted."""

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            built["counters"] = getattr(self, "counters", dict)()
            arrays = [self.layout.doc, self.layout.woff, self.layout.mask,
                      self.state.cdk, self.state.ckt, self.state.z]
            built["devices"] = min(len(x.devices()) for x in arrays)

        def gather_counts(self):
            # harness/train.py gathers once, right after the window
            built["peaks"] = [
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in jax.devices()[:chips]]
            return super().gather_counts()

    # harness/train.py looks the engine up when it runs
    engine.ModelParallelLDA = Counted
    try:
        out = train.run(jax, cfg, mix, seed, seconds, chips, trace_dir)
    finally:
        engine.ModelParallelLDA = real

    n_iter = out.counts["iterations"]
    for name, val in built["counters"].items():
        out.counts[name] = n_iter * val
    out.counts.update(vocab_size=cfg["vocab_size"],
                      num_topics=cfg["num_topics"],
                      num_workers=cfg["num_workers"],
                      blocks_per_worker=cfg["blocks_per_worker"])
    out.check_input = types.SimpleNamespace(
        **vars(out.check_input), num_workers=cfg["num_workers"],
        blocks_per_worker=cfg["blocks_per_worker"],
        doc_to_worker=cfg["doc_to_worker"])
    out.notes["array_devices_at_build"] = built["devices"]
    out.notes["memory_peak_bytes_per_device"] = built["peaks"]
    return out
