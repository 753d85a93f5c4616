"""CLI sampler selection: registry-derived choices + the ``auto`` probe.

The launch drivers (`lda_train`, `lda_infer`) used to hard-code their
``--sampler`` choice lists, so every new registry sampler meant touching
every CLI.  Choices now come from the engine registry itself
(`engine/rounds.py`), plus the pseudo-sampler ``auto``:

* ``auto`` picks the sampler FAMILY from a regime map of K × doc-len
  cells: nearest cell in (log₂ K, log₂ max-doc-len) space decides
  between ``scan``, ``mh``, and ``sparse``.  The map was derived from
  CPU timings and is unmeasured on the chip (ROADMAP design debt 3).
  Callers pass the workload's ``num_topics``/``max_doc_len``; without
  them, ``auto`` falls back to the MH family (the old behaviour).
* ``auto`` then resolves the chosen family per platform: the Pallas
  kernel form on TPU, the jnp twin elsewhere.  The pairs draw
  identically, so the platform leg never changes a chain — only which
  compiled form runs it.
* Off TPU, an EXPLICITLY requested ``*_pallas`` sampler runs the kernel
  in interpret mode — correct (the bit-identity tests rely on it) but
  orders of magnitude slower than the jnp twin at real workload sizes,
  so the drivers refuse it unless ``--force`` is given.
"""
from __future__ import annotations

import math

# Winners of a K × max-doc-len sweep timed on the CPU (Vb=64, 8k tokens,
# Zipf 1.1); unmeasured on the chip (ROADMAP design debt 3).  Keys are
# the swept (K, doc_len) grid points; lookups snap to the nearest cell in
# log2 space, since both axes are scale parameters.
REGIME_MAP = {
    (256, 16): "sparse", (256, 48): "sparse", (256, 256): "mh",
    (4096, 16): "scan", (4096, 48): "sparse", (4096, 256): "scan",
    (16384, 16): "sparse", (16384, 48): "sparse", (16384, 256): "sparse",
}

# jnp form -> Pallas kernel form of the same chain (draw-identical
# pairs).  "scan" is the exact kernel and has no frozen-count Pallas
# twin, so it runs as-is everywhere.
_PALLAS_TWIN = {"mh": "mh_pallas", "sparse": "sparse_pallas"}


def regime_sampler(num_topics: int, max_doc_len: int) -> str:
    """Sampler family for a workload: nearest :data:`REGIME_MAP` cell in
    (log₂ K, log₂ max-doc-len) space; grid-exact at the measured points."""
    lk = math.log2(max(int(num_topics), 1))
    ll = math.log2(max(int(max_doc_len), 1))
    cell = min(REGIME_MAP,
               key=lambda c: ((math.log2(c[0]) - lk) ** 2
                              + (math.log2(c[1]) - ll) ** 2, c))
    return REGIME_MAP[cell]


def train_sampler_choices() -> list:
    """``--sampler`` choices for training: every registered engine
    sampler, plus ``auto``."""
    from repro.core.engine.rounds import available_samplers
    return available_samplers() + ["auto"]


def infer_sampler_choices() -> list:
    """``--sampler`` choices for fold-in/serving: ``scan``, the
    table-capable family, the sparse family, plus ``auto`` — i.e. every
    registry sampler `infer.fold_in` can run against a frozen snapshot."""
    from repro.core.engine.rounds import available_samplers, table_capable
    names = ["scan"] + [m for m in available_samplers()
                        if table_capable(m)
                        or m in ("sparse", "sparse_pallas")]
    return names + ["auto"]


def resolve_sampler_choice(name: str, *, force: bool = False,
                           num_topics: int | None = None,
                           max_doc_len: int | None = None,
                           auto_tpu: str = "mh_pallas",
                           auto_default: str = "mh") -> str:
    """Resolve a CLI ``--sampler`` value to a registry sampler name.

    ``auto`` with the workload's ``num_topics``/``max_doc_len`` picks the
    family from the measured :data:`REGIME_MAP` (so the drivers must
    resolve AFTER the corpus exists), then the Pallas form of that family
    on TPU and the jnp form elsewhere (draw-identical either way).
    Without workload parameters it falls back to ``auto_tpu`` /
    ``auto_default`` — the pre-regime-map behaviour.  An explicit
    ``*_pallas`` off TPU exits with guidance unless ``force`` —
    interpret mode is a validation vehicle, not a serving path.
    """
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if name == "auto":
        if num_topics is not None and max_doc_len is not None:
            family = regime_sampler(num_topics, max_doc_len)
            return (_PALLAS_TWIN.get(family, family) if on_tpu
                    else family)
        return auto_tpu if on_tpu else auto_default
    if name.endswith("_pallas") and not on_tpu and not force:
        raise SystemExit(
            f"--sampler {name}: Pallas kernels run in interpret mode on "
            f"{jax.default_backend()!r} — orders of magnitude slower at "
            f"real sizes. Use --sampler auto, the "
            f"jnp twin {name.removesuffix('_pallas')!r}, or pass --force "
            f"to run interpret mode anyway.")
    return name


# ---------------------------------------------------------------------------
# CountStore selection (DESIGN.md §16)
# ---------------------------------------------------------------------------

def store_choices() -> list:
    """``--store`` choices: every registered CountStore kind, plus
    ``auto`` (regime-derived)."""
    from repro.core.engine.countstore import available_stores
    return available_stores() + ["auto"]


def resolve_store_choice(name: str, *,
                         num_topics: int | None = None,
                         max_doc_len: int | None = None) -> str:
    """Resolve a CLI ``--store`` value to a registered CountStore kind.

    ``auto`` reuses the measured :data:`REGIME_MAP`: the tail store pays
    off exactly where the sparse sampler family does — long-tailed
    word-topic rows whose nnz ≪ K — so ``auto`` picks ``tail`` iff the
    regime probe picks the sparse family for this workload, and the
    bitwise-frozen ``dense`` default otherwise (also the fallback when
    the workload parameters are unknown, e.g. before the corpus exists).
    The choice never affects the chain — stores are draw-equivalent by
    construction — only memory/layout, so resolving it per-workload is
    always safe.
    """
    from repro.core.engine.countstore import available_stores
    if name == "auto":
        if num_topics is not None and max_doc_len is not None:
            family = regime_sampler(num_topics, max_doc_len)
            return "tail" if family == "sparse" else "dense"
        return "dense"
    if name not in available_stores():
        raise SystemExit(
            f"--store {name}: unknown store kind; "
            f"choices: {store_choices()}")
    return name
