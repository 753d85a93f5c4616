"""Names and host spans of the program's own tracing.

Host spans are ``jax.profiler.TraceAnnotation`` events: they land in the
profiler's trace on the thread that opens them, on the same clock as the
device planes, and cost under a microsecond each when no trace is being
recorded.  Device scopes are ``jax.named_scope`` names: they enter the
JAX name stack of every op traced under them, which the profiler shows
as each device op's ``tf_op``, and cost nothing on the device.

Counters are plain numbers the program reports by name; training's
(``ModelParallelLDA.counters``) say what one iteration does: slots
sampled, real tokens, bytes handed on through the ring.

Readers match on exact names, so a span's arguments never enter its
name: :func:`span` passes them as the event's stats, and only while a
trace is being recorded.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

# -- device scopes of one training iteration (``core/engine/backends.py``)
DOC_TABLES = "lda.doc_tables"     # per-iteration doc-proposal tables
WORD_TABLES = "lda.word_tables"   # word-proposal tables of resident blocks
SAMPLE = "lda.sample"             # the worker round: gathers, kernel, writes
RECONCILE = "lda.reconcile"       # delta psum of replica block copies
ROTATE = "lda.rotate"             # roll / ppermute of blocks, ids, tables
CK_SYNC = "lda.ck_sync"           # C_k sum or psum (and the drift error)
QUEUE = "lda.queue"               # slot-queue concatenates
SCOPES = (DOC_TABLES, WORD_TABLES, SAMPLE, RECONCILE, ROTATE, CK_SYNC,
          QUEUE)

# -- host spans of serving (``serve/scheduler.py``, ``serve/topic_infer.py``,
# ``core/infer.py``)
SERVE_BATCH = "serve.batch"       # one dispatched batch
SERVE_DRAWS = "serve.draws"       # the batch's seed-contract draws
FOLDIN_PACK = "foldin.pack"       # bucket packing of the queries and draws
FOLDIN_PLACE = "foldin.place"     # a snapshot's operands put on the device
FOLDIN_UPLOAD = "foldin.upload"   # host -> device conversions of a query
FOLDIN_RUN = "foldin.run"         # the call of the jitted sweeps
FOLDIN_FETCH = "foldin.fetch"     # the host blocked on the device's result
FOLDIN_THETA = "foldin.theta"     # mixtures from the fetched counts

# -- host spans of training (``core/engine/api.py``)
TRAIN_PLACE = "train.place"         # layout and state put on the device(s)
TRAIN_UNIFORMS = "train.uniforms"   # the iteration's uniforms, drawn and put
TRAIN_DISPATCH = "train.dispatch"   # the call into the jitted iteration

SPANS = (SERVE_BATCH, SERVE_DRAWS, FOLDIN_PACK, FOLDIN_PLACE, FOLDIN_UPLOAD,
         FOLDIN_RUN, FOLDIN_FETCH, FOLDIN_THETA, TRAIN_PLACE, TRAIN_UNIFORMS,
         TRAIN_DISPATCH)

# -- per-iteration counters of training (``ModelParallelLDA.counters``)
SLOTS = "slots"                   # token slots sampled, padding included
REAL_TOKENS = "real_tokens"       # the corpus's tokens
ROTATE_BYTES = "rotate_bytes"     # bytes one worker hands on in the ring
COUNTERS = (SLOTS, REAL_TOKENS, ROTATE_BYTES)


def span(name: str, **args) -> TraceAnnotation:
    """A host span ``name`` for a ``with`` block; ``args`` become the
    event's stats while a trace is recorded and are dropped otherwise.
    More stats can be added inside the block with ``set_metadata``."""
    if args and TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **args)
    return TraceAnnotation(name)

