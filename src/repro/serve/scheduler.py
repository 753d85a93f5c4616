"""Event-loop serving scheduler: continuous batching, hot-query cache,
multi-replica dispatch, zero-downtime snapshot hot-swap (DESIGN.md §14).

`TopicInferenceServer` answers one batch at a time; a system serving
heavy traffic needs the layer ABOVE it — the thing that decides, every
tick, which queued requests become the next fold-in batch.  That layer
is :class:`ServingScheduler`:

* **admission control** — a bounded FIFO queue; a submission that can't
  be served is rejected immediately with a reason (``queue_full``,
  ``empty``, ``too_long``, ``bad_word_id``) instead of silently queueing
  into unbounded latency.
* **continuous batching** — each :meth:`~ServingScheduler.tick` forms
  fold-in batches from whatever is queued right now (FIFO prefix, capped
  at ``max_batch``), reusing the server's jit-per-bucket pads.  An
  optional ``max_batch_delay`` holds a partial batch to fill, but never
  past the deadline — the no-starvation knob.
* **hot-query cache** — responses are cached keyed on the token
  MULTISET; a hit is bitwise-equal to a fresh fold-in because responses
  are pure functions of (snapshot, multiset, seed) — see the seed
  contract below.
* **multi-replica dispatch** — batches round-robin across ``N`` server
  replicas sharing one snapshot (frozen-model serving is embarrassingly
  data-parallel, §11), so replicas are a pure throughput knob.
* **replica resilience** (DESIGN.md §15) — per-replica consecutive-
  failure circuit breakers (closed → open → half-open probe), bounded
  retry-on-alternate-replica that stays bitwise-invisible (draws are
  keyed on content, not on which replica ran), per-request deadline
  expiry and all-breakers-open load shedding as structured rejections,
  and fingerprint-gated hot-swap that refuses a corrupt candidate while
  the old epoch keeps serving.
* **zero-downtime hot-swap** — :meth:`~ServingScheduler.swap_snapshot`
  installs the next training snapshot as a pointer flip: requests
  admitted before the swap complete on the snapshot they were admitted
  under, new admissions bind the new one, and every response is stamped
  with its swap epoch + snapshot fingerprint.  No queue flush, no
  barrier, no dropped or epoch-mixed response — proven bitwise in
  ``tests/test_scheduler.py``.

**The seed contract.**  Every request's randomness is derived from
``(scheduler seed, snapshot fingerprint, token-multiset digest)`` and
the request's tokens are canonicalized (sorted) before fold-in — topic
mixtures are exchangeable in token order, so the sort is statistically
inert.  With `TopicInferenceServer.infer_with_draws` feeding those
per-request draws into the padded batch (pad invariance makes every
other slot inert), a response is a PURE FUNCTION of (snapshot contents,
token multiset, seed): independent of batch composition, bucket, queue
state, replica, and wall time.  :func:`reference_theta` computes that
function standalone; every scheduler response — batched, cached,
mid-swap, any replica — must equal it bitwise, which is what makes every
scheduler property a bitwise-testable one.

**Time is injected.**  The scheduler never calls ``time`` directly; it
reads a :class:`Clock`.  Tests drive a :class:`VirtualClock` (no
wall-clock sleeps anywhere, fully deterministic replay); the traffic
benchmark and the ``lda_serve`` CLI drive a :class:`WallClock`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import OrderedDict, deque
from typing import Deque, Dict, List, Optional, Sequence

import numpy as np

from repro import tracing
from repro.core import faults
from repro.core.infer import DEFAULT_FOLD_IN_SWEEPS, ModelSnapshot
from repro.data.integrity import CorruptArtifactError
from repro.serve.topic_infer import TopicInferenceServer, bucket_size


# ---------------------------------------------------------------------------
# Injected time
# ---------------------------------------------------------------------------

class Clock:
    """Time-source protocol: ``now() -> float`` seconds and
    ``sleep(dt)``.  Injected so the scheduler is deterministic under a
    virtual clock in tests and runs under wall time in production."""

    def now(self) -> float:
        raise NotImplementedError

    def sleep(self, dt: float) -> None:
        raise NotImplementedError


class WallClock(Clock):
    """Monotonic wall time — the benchmark/CLI clock."""

    def now(self) -> float:
        return time.monotonic()

    def sleep(self, dt: float) -> None:
        if dt > 0:
            time.sleep(dt)


class VirtualClock(Clock):
    """Deterministic test clock: time moves ONLY when the test (or an
    open-loop replay's idle step) advances it."""

    def __init__(self, start: float = 0.0):
        self._t = float(start)

    def now(self) -> float:
        return self._t

    def sleep(self, dt: float) -> None:
        self.advance(dt)

    def advance(self, dt: float) -> float:
        if dt < 0:
            raise ValueError(f"cannot advance time by {dt}")
        self._t += float(dt)
        return self._t


# ---------------------------------------------------------------------------
# The seed contract: canonical tokens, multiset digest, per-request draws
# ---------------------------------------------------------------------------

def canonical_tokens(tokens: Sequence[int]) -> np.ndarray:
    """Sorted int32 token ids — the canonical form of a query.  Fold-in
    runs on this form, so two permutations of the same multiset are the
    SAME request (same draws, same response, same cache slot)."""
    return np.sort(np.asarray(tokens, np.int32).ravel())


def multiset_digest(canon: np.ndarray) -> bytes:
    """16-byte identity of a token multiset (sha256 of the canonical
    form).  The cache uses it as the slot key but verifies the stored
    canonical array on every hit, so a collision degrades to a miss —
    never to the wrong answer."""
    return hashlib.sha256(canon.tobytes()).digest()[:16]


def request_draws(seed: int, fingerprint: str, digest: bytes, n: int,
                  num_topics: int, num_sweeps: int):
    """Per-request fold-in randomness: ``(z0 [n], u [num_sweeps, n])``
    derived from (scheduler seed, snapshot fingerprint, multiset digest).
    Including the fingerprint re-keys every request's chain on swap —
    same doc, new model, fresh draws — while keeping the response a pure
    function of content, never of epoch numbering or arrival time."""
    ss = np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFFFFFFFFFF, int(fingerprint, 16),
         int.from_bytes(digest, "big")])
    rng = np.random.default_rng(ss)
    z0 = rng.integers(0, num_topics, size=n).astype(np.int32)
    u = rng.random((num_sweeps, n), dtype=np.float32)
    return z0, u


def reference_theta(snapshot: ModelSnapshot, tokens: Sequence[int], *,
                    sampler: str = "scan",
                    num_sweeps: int = DEFAULT_FOLD_IN_SWEEPS,
                    seed: int = 0) -> np.ndarray:
    """Serve ONE request outside any scheduler: the pure function of
    (snapshot contents, token multiset, seed contract) that every
    scheduler response must equal bitwise — batched or alone, cached or
    fresh, before or after any number of swaps, on any replica.  The
    hot-swap and cache equivalence tests anchor on this.  An installed
    snapshot is already on the device, so this places nothing new."""
    canon = canonical_tokens(tokens)
    z0, u = request_draws(seed, snapshot.fingerprint(),
                          multiset_digest(canon), canon.size,
                          snapshot.num_topics, num_sweeps)
    server = TopicInferenceServer(snapshot, sampler=sampler,
                                  num_sweeps=num_sweeps, seed=seed)
    return server.infer_with_draws([canon], [z0], [u])[0]


# ---------------------------------------------------------------------------
# Hot-query cache
# ---------------------------------------------------------------------------

class QueryCache:
    """LRU response cache keyed on the token multiset.

    Correctness rests on the seed contract, not on trust: an entry is
    only ever written by a fold-in under the CURRENT snapshot, the
    scheduler clears the cache on swap (entries are epoch-bound), and a
    hit verifies the stored canonical token array against the query's
    (collision check) — so a hit is bitwise the fold-in the scheduler
    would otherwise run."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._entries: "OrderedDict[bytes, tuple]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.collisions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, digest: bytes, canon: np.ndarray
            ) -> Optional[np.ndarray]:
        ent = self._entries.get(digest)
        if ent is not None:
            stored, theta = ent
            if stored.shape == canon.shape and \
                    np.array_equal(stored, canon):
                self._entries.move_to_end(digest)
                self.hits += 1
                return theta
            self.collisions += 1         # digest matched, multiset didn't
        self.misses += 1
        return None

    def put(self, digest: bytes, canon: np.ndarray,
            theta: np.ndarray) -> None:
        if self.capacity <= 0:
            return
        self._entries[digest] = (canon, theta)
        self._entries.move_to_end(digest)
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
            self.evictions += 1

    def clear(self) -> None:
        self._entries.clear()


# ---------------------------------------------------------------------------
# Requests and responses
# ---------------------------------------------------------------------------

REJECT_QUEUE_FULL = "queue_full"
REJECT_EMPTY = "empty"
REJECT_TOO_LONG = "too_long"
REJECT_BAD_WORD = "bad_word_id"
REJECT_SHED = "shed"                       # all replica breakers open
REJECT_DEADLINE = "deadline_expired"       # waited past request_deadline
REJECT_REPLICA = "replica_failure"         # retry budget exhausted

BREAKER_CLOSED = "closed"
BREAKER_OPEN = "open"
BREAKER_HALF_OPEN = "half_open"

# ``batch_log`` keeps the newest this many dispatched batches: bounded for
# a long-running server, and more than a benchmark window dispatches
BATCH_LOG_LEN = 65536


@dataclasses.dataclass
class ReplicaHealth:
    """Per-replica circuit breaker (DESIGN.md §15).  CLOSED routes
    traffic normally; ``breaker_threshold`` CONSECUTIVE failures open
    it; an OPEN breaker takes no traffic until ``breaker_cooldown`` has
    passed, then transitions to HALF_OPEN and admits one probe batch —
    success closes it, failure re-opens (and restarts the cooldown).
    Health is keyed on the replica SLOT, not the snapshot epoch: a sick
    process stays sick across hot-swaps."""
    state: str = BREAKER_CLOSED
    consecutive_failures: int = 0
    failures: int = 0
    successes: int = 0
    opens: int = 0
    opened_at: float = 0.0

    def record_failure(self, now: float, threshold: int) -> None:
        self.failures += 1
        self.consecutive_failures += 1
        if self.state == BREAKER_HALF_OPEN or \
                (self.state == BREAKER_CLOSED
                 and self.consecutive_failures >= threshold):
            self.state = BREAKER_OPEN
            self.opened_at = now
            self.opens += 1

    def record_success(self) -> None:
        self.successes += 1
        self.consecutive_failures = 0
        self.state = BREAKER_CLOSED

    def available(self, now: float, cooldown: float) -> bool:
        """Lazy open -> half_open transition: state machines driven by
        the injected clock have no timers, only reads."""
        if self.state == BREAKER_OPEN and now - self.opened_at >= cooldown:
            self.state = BREAKER_HALF_OPEN
        return self.state != BREAKER_OPEN


@dataclasses.dataclass
class _Pending:
    """A queued request: bound to the epoch current at ADMISSION — the
    hot-swap invariant lives here."""
    req_id: int
    canon: np.ndarray
    digest: bytes
    epoch: int
    t_arrival: float
    retries: int = 0


@dataclasses.dataclass
class Response:
    """One answer per submission.  ``epoch``/``fingerprint`` stamp which
    installed snapshot produced ``theta``; timings use the injected
    clock (``t_arrival`` ≤ ``t_dispatch`` ≤ ``t_finish``)."""
    req_id: int
    status: str                        # "ok" | "rejected"
    reason: str = ""                   # rejection reason when rejected
    theta: Optional[np.ndarray] = None
    epoch: int = -1
    fingerprint: str = ""
    replica: int = -1
    cached: bool = False
    t_arrival: float = 0.0
    t_dispatch: float = 0.0
    t_finish: float = 0.0

    @property
    def latency(self) -> float:
        return self.t_finish - self.t_arrival


# ---------------------------------------------------------------------------
# The scheduler
# ---------------------------------------------------------------------------

class ServingScheduler:
    """Event-loop continuous-batching scheduler over
    `TopicInferenceServer` replicas (module docstring; DESIGN.md §14).

    The driving loop is external (`serve/traffic.py` replay, the
    ``lda_serve`` CLI, or a test): call :meth:`submit` as requests
    arrive, :meth:`tick` to let the scheduler act, :meth:`swap_snapshot`
    when training publishes a new model.  Nothing here sleeps or reads
    wall time — all timing flows through the injected clock.
    """

    def __init__(self, snapshot: ModelSnapshot, *, sampler: str = "scan",
                 num_sweeps: int = DEFAULT_FOLD_IN_SWEEPS, seed: int = 0,
                 num_replicas: int = 1, max_queue: int = 64,
                 max_batch: int = 8, max_batch_delay: float = 0.0,
                 max_doc_tokens: Optional[int] = None,
                 cache_capacity: int = 256, clock: Optional[Clock] = None,
                 min_batch_bucket: int = 1, min_token_bucket: int = 8,
                 breaker_threshold: int = 3, breaker_cooldown: float = 1.0,
                 max_retries: int = 2,
                 request_deadline: Optional[float] = None,
                 fault_plan: Optional[faults.FaultPlan] = None):
        if num_replicas < 1:
            raise ValueError(f"num_replicas must be >= 1, got {num_replicas}")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.sampler = sampler
        self.num_sweeps = int(num_sweeps)
        self.seed = int(seed)
        self.num_replicas = int(num_replicas)
        self.max_queue = int(max_queue)
        self.max_batch = int(max_batch)
        self.max_batch_delay = float(max_batch_delay)
        self.max_doc_tokens = max_doc_tokens
        self.min_batch_bucket = int(min_batch_bucket)
        self.min_token_bucket = int(min_token_bucket)
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_cooldown = float(breaker_cooldown)
        self.max_retries = int(max_retries)
        self.request_deadline = request_deadline
        self.fault_plan = fault_plan
        self.clock = clock if clock is not None else WallClock()
        self.cache = QueryCache(cache_capacity)

        self.epoch = 0
        self._snapshots: Dict[int, ModelSnapshot] = {}
        self._servers: Dict[int, List[TopicInferenceServer]] = {}
        self._fp: Dict[int, str] = {}
        self._install(snapshot)

        self._queue: Deque[_Pending] = deque()
        self._rr = 0                        # round-robin batch counter
        self._next_id = 0
        self.results: Dict[int, Response] = {}
        # the newest BATCH_LOG_LEN dispatched batches; ``batches`` counts
        # them all
        self.batch_log: Deque[dict] = deque(maxlen=BATCH_LOG_LEN)
        self.batches = 0
        self.submitted = 0
        self.admitted = 0
        self.served = 0
        self.cache_hits = 0
        self.swaps = 0
        self.rejections: Dict[str, int] = {}
        # resilience state (DESIGN.md §15): health is per replica SLOT
        self.health = [ReplicaHealth() for _ in range(self.num_replicas)]
        self.retries = 0                   # re-dispatch attempts
        self.replica_failures = 0          # failed dispatch attempts
        self.shed = 0                      # admissions refused: all open
        self.deadline_expired = 0
        self.failed_admitted = 0           # admitted -> structured reject

    # -- model installation / hot-swap ------------------------------------
    def _install(self, snapshot: ModelSnapshot) -> None:
        # replicas share ONE snapshot object, so per-snapshot derived
        # state (alias tables, sparse cumsums) and its device copy are
        # built and placed once and pointed to N times — a replica is
        # pure compute, not memory
        self._snapshots[self.epoch] = snapshot
        self._fp[self.epoch] = snapshot.fingerprint()
        self._servers[self.epoch] = [
            TopicInferenceServer(snapshot, sampler=self.sampler,
                                 num_sweeps=self.num_sweeps, seed=self.seed,
                                 min_batch_bucket=self.min_batch_bucket,
                                 min_token_bucket=self.min_token_bucket)
            for _ in range(self.num_replicas)]

    def warm(self, max_doc_len: int) -> int:
        """Compile every power-of-two (batch, token) bucket reachable
        for docs up to ``max_doc_len`` — the serving cold-start, done
        once before traffic.  The jit cache is keyed on shapes (the
        snapshot is a runtime argument), so one pass through the current
        epoch's first replica covers every replica AND every snapshot a
        later swap installs.  Returns the bucket count."""
        server = self._servers[self.epoch][0]
        n = 0
        qb = 1
        q_cap = bucket_size(self.max_batch, self.min_batch_bucket)
        t_cap = bucket_size(max(int(max_doc_len), 1),
                            self.min_token_bucket)
        while qb <= q_cap:
            tb = self.min_token_bucket
            while tb <= t_cap:
                server.infer([np.zeros(tb, np.int32)] * qb)
                n += 1
                tb <<= 1
            qb <<= 1
        return n

    def swap_snapshot(self, snapshot: ModelSnapshot,
                      expect_fingerprint: Optional[str] = None) -> int:
        """Install the next training snapshot with zero downtime.

        A pointer flip: the new epoch's replicas are created, new
        admissions bind them immediately, and requests already admitted
        (queued or in flight) complete against the snapshot stamped on
        them at admission — the old epoch's servers, and its snapshot's
        device copy, are released only once its last queued request
        drains.  The new snapshot is placed on the device here, so until
        then both are resident.  The cache is cleared: its
        entries answer for the previous fingerprint.  Returns the new
        epoch.

        ``expect_fingerprint`` is the swap's integrity gate (§15): when
        the caller knows what it exported (trainer-published manifest),
        a candidate whose content fingerprint disagrees — torn copy, bit
        rot, wrong file — is REFUSED with :class:`CorruptArtifactError`
        before any state changes, and the old epoch keeps serving."""
        if expect_fingerprint is not None:
            got = snapshot.fingerprint()
            if got != expect_fingerprint:
                raise CorruptArtifactError(
                    "<candidate snapshot>",
                    f"snapshot fingerprint {got} != expected "
                    f"{expect_fingerprint}; refusing hot-swap")
        self.epoch += 1
        self._install(snapshot)
        self.cache.clear()
        self.swaps += 1
        self._release_drained_epochs()
        return self.epoch

    def _release_drained_epochs(self) -> None:
        # a drained epoch's device copy is freed here, unless a live
        # epoch installed the same snapshot object
        live = {p.epoch for p in self._queue} | {self.epoch}
        for e in [e for e in self._servers if e not in live]:
            del self._servers[e]
            snap = self._snapshots.pop(e)
            if not any(s is snap for s in self._snapshots.values()):
                snap.release_device()

    @property
    def snapshot(self) -> ModelSnapshot:
        return self._snapshots[self.epoch]

    @property
    def fingerprint(self) -> str:
        return self._fp[self.epoch]

    @property
    def pending(self) -> int:
        return len(self._queue)

    # -- admission ---------------------------------------------------------
    def _reject(self, rid: int, reason: str, now: float) -> int:
        self.rejections[reason] = self.rejections.get(reason, 0) + 1
        self.results[rid] = Response(rid, "rejected", reason=reason,
                                     epoch=self.epoch, t_arrival=now,
                                     t_dispatch=now, t_finish=now)
        return rid

    def submit(self, tokens: Sequence[int],
               now: Optional[float] = None) -> int:
        """Admit (or reject) one query; returns its request id.  The
        outcome lands in ``results[rid]`` — immediately for rejections
        and cache hits, after a future tick otherwise.  ``now`` defaults
        to the clock but is overridable so an open-loop replay can stamp
        the SCHEDULED arrival time (queueing delay then shows up in
        latency even when the submitting loop itself fell behind)."""
        now = float(self.clock.now() if now is None else now)
        rid = self._next_id
        self._next_id += 1
        self.submitted += 1
        tokens = np.asarray(tokens, np.int32).ravel()
        if tokens.size == 0:
            return self._reject(rid, REJECT_EMPTY, now)
        if self.max_doc_tokens is not None and \
                tokens.size > self.max_doc_tokens:
            return self._reject(rid, REJECT_TOO_LONG, now)
        # ids must index the RESIDENT rows (row-restricted snapshots
        # serve remapped ids, so the bound is the local row count)
        if int(tokens.min()) < 0 or \
                int(tokens.max()) >= self.snapshot.vocab_size:
            return self._reject(rid, REJECT_BAD_WORD, now)
        canon = canonical_tokens(tokens)
        digest = multiset_digest(canon)
        theta = self.cache.get(digest, canon)
        if theta is not None:
            # a hit costs no queue slot, so hot queries are served even
            # when admission is otherwise rejecting (overload shedding
            # never sheds the traffic the cache already paid for)
            self.admitted += 1
            self.served += 1
            self.cache_hits += 1
            self.results[rid] = Response(
                rid, "ok", theta=theta.copy(), epoch=self.epoch,
                fingerprint=self._fp[self.epoch], cached=True,
                t_arrival=now, t_dispatch=now, t_finish=now)
            return rid
        if len(self._queue) >= self.max_queue:
            return self._reject(rid, REJECT_QUEUE_FULL, now)
        if not self._available_replicas(now):
            # load shedding: every breaker is open, so an admission now
            # could only rot in the queue — refuse it loudly instead.
            # (After the cache check on purpose: hits cost no replica.)
            self.shed += 1
            return self._reject(rid, REJECT_SHED, now)
        self.admitted += 1
        self._queue.append(_Pending(rid, canon, digest, self.epoch, now))
        return rid

    # -- the event loop body ----------------------------------------------
    def tick(self, flush: bool = False) -> List[Response]:
        """Dispatch every batch that is ready NOW; returns the responses
        completed this tick, in FIFO order.

        A batch is the FIFO prefix of the queue sharing the head's epoch
        (a fold-in binds exactly one snapshot), capped at ``max_batch``.
        It dispatches when it is full, when its oldest member has waited
        ``max_batch_delay`` (the starvation deadline), when its epoch is
        closed (a swap happened, so the group can never grow), or when
        ``flush`` forces it.  With ``max_batch_delay == 0`` every tick
        serves everything queued — pure continuous batching."""
        out: List[Response] = []
        self._expire_deadlines(self.clock.now())
        while self._queue:
            now = self.clock.now()
            if not self._available_replicas(now):
                break                     # every breaker open: hold FIFO
            head = self._queue[0]
            group = 1
            while (group < len(self._queue) and group < self.max_batch
                   and self._queue[group].epoch == head.epoch):
                group += 1
            epoch_closed = head.epoch != self.epoch
            if not (flush or epoch_closed or group >= self.max_batch
                    or now - head.t_arrival >= self.max_batch_delay):
                break
            batch = [self._queue.popleft() for _ in range(group)]
            responses, ok = self._run_batch(batch, now)
            out.extend(responses)
            if not ok:
                # total dispatch failure: survivors are back at the queue
                # head; stop this tick so one tick can't spin forever on
                # a batch no replica will take
                break
        self._release_drained_epochs()
        return out

    # -- resilience --------------------------------------------------------
    def _available_replicas(self, now: float) -> List[int]:
        return [i for i, h in enumerate(self.health)
                if h.available(now, self.breaker_cooldown)]

    def _expire_deadlines(self, now: float) -> None:
        if self.request_deadline is None:
            return
        keep: Deque[_Pending] = deque()
        for p in self._queue:
            if now - p.t_arrival >= self.request_deadline:
                self.deadline_expired += 1
                self._reject_admitted(p, REJECT_DEADLINE, now)
            else:
                keep.append(p)
        self._queue = keep

    def _reject_admitted(self, p: _Pending, reason: str,
                         now: float) -> None:
        """Structured post-admission rejection: the request got a queue
        slot but the system could not serve it (deadline passed, retry
        budget exhausted).  Counted separately from admission-time
        rejects so ``dropped()`` still means 'vanished without ANY
        outcome'."""
        self.rejections[reason] = self.rejections.get(reason, 0) + 1
        self.failed_admitted += 1
        self.results[p.req_id] = Response(
            p.req_id, "rejected", reason=reason, epoch=p.epoch,
            t_arrival=p.t_arrival, t_dispatch=now, t_finish=now)

    def _fire_replica(self, replica: int, epoch: int) -> None:
        """Fault-injection hook around one dispatch attempt: scripted
        replica failures raise here; scripted slowness is charged to the
        injected clock (latency, not error)."""
        detail = f"replica:{replica},epoch:{epoch}"
        plan = self.fault_plan if self.fault_plan is not None \
            else faults.active()
        if plan is None:
            return
        dt = plan.delay("replica", detail)
        if dt > 0:
            self.clock.sleep(dt)
        plan.fire("replica", detail)

    def drain(self) -> List[Response]:
        """Force-dispatch everything queued (end of a replay)."""
        return self.tick(flush=True)

    def _run_batch(self, batch: List[_Pending],
                   t_dispatch: float) -> "tuple[List[Response], bool]":
        """Dispatch one batch, retrying on alternate replicas on failure.

        Returns ``(responses, ok)``.  Retries are bitwise-invisible: the
        draws are keyed on (seed, fingerprint, digest) — never on which
        replica ran — so the answer from attempt 3 on replica 2 is the
        answer attempt 1 would have produced (pinned by
        ``tests/test_scheduler_resilience.py``).  When every available
        replica fails, each request's retry budget is charged: survivors
        requeue at the FRONT (FIFO order preserved), exhausted ones get
        a structured ``replica_failure`` rejection."""
        epoch = batch[0].epoch
        assert all(p.epoch == epoch for p in batch)   # one snapshot/batch
        servers = self._servers[epoch]
        docs = [p.canon for p in batch]
        bucket = servers[0].bucket_shape(docs)
        with tracing.span(tracing.SERVE_BATCH, size=len(batch),
                          bucket=bucket, epoch=epoch,
                          first=batch[0].req_id) as span:
            h2d_before = sum(s.h2d_bytes for s in servers)
            theta, replica = self._dispatch(batch, docs, t_dispatch)
            span.set_metadata(replica=replica)
        if theta is None:
            # every available replica refused this batch: charge each
            # request's retry budget and requeue the survivors in order
            now = self.clock.now()
            survivors = []
            for p in batch:
                p.retries += 1
                if p.retries > self.max_retries:
                    self._reject_admitted(p, REJECT_REPLICA, now)
                else:
                    survivors.append(p)
            self._queue.extendleft(reversed(survivors))
            return [], False
        t_finish = self.clock.now()
        self.batches += 1
        self.batch_log.append({
            "epoch": epoch, "size": len(batch), "replica": replica,
            "bucket": bucket, "t_dispatch": t_dispatch,
            "h2d_bytes": sum(s.h2d_bytes for s in servers) - h2d_before,
            "wait_s": sum(t_dispatch - p.t_arrival for p in batch),
            "t_finish": t_finish})
        fp = self._fp[epoch]
        responses = []
        for i, p in enumerate(batch):
            resp = Response(p.req_id, "ok", theta=theta[i], epoch=epoch,
                            fingerprint=fp, replica=replica, cached=False,
                            t_arrival=p.t_arrival, t_dispatch=t_dispatch,
                            t_finish=t_finish)
            self.results[p.req_id] = resp
            responses.append(resp)
            self.served += 1
            if epoch == self.epoch:      # never cache for a dead epoch
                self.cache.put(p.digest, p.canon, theta[i])
        return responses, True

    def _dispatch(self, batch: List[_Pending], docs: List[np.ndarray],
                  t_dispatch: float) -> "tuple[Optional[np.ndarray], int]":
        """Draw the batch's randomness and try the available replicas in
        round-robin order; returns ``(theta, replica)``, or ``(None, -1)``
        when every one of them failed."""
        epoch = batch[0].epoch
        servers = self._servers[epoch]
        fp = self._fp[epoch]
        with tracing.span(tracing.SERVE_DRAWS):
            draws = [request_draws(self.seed, fp, p.digest, p.canon.size,
                                   servers[0].snapshot.num_topics,
                                   self.num_sweeps)
                     for p in batch]
        avail = self._available_replicas(t_dispatch)
        start = self._rr % max(len(avail), 1)
        self._rr += 1
        candidates = avail[start:] + avail[:start]
        for attempt, rid in enumerate(candidates):
            if attempt > 0:
                self.retries += 1
            now = self.clock.now()
            try:
                self._fire_replica(rid, epoch)
                theta = servers[rid].infer_with_draws(
                    docs, [d[0] for d in draws], [d[1] for d in draws])
            except Exception:
                self.replica_failures += 1
                self.health[rid].record_failure(now,
                                                self.breaker_threshold)
                continue
            self.health[rid].record_success()
            return theta, rid
        return None, -1

    # -- observability -----------------------------------------------------
    def ok_responses(self) -> List[Response]:
        return [r for r in self.results.values() if r.status == "ok"]

    def dropped(self) -> int:
        """Admitted requests that vanished with NO outcome — neither an
        ok response nor a structured post-admission rejection.  MUST be
        zero once the queue drains (the hot-swap acceptance criterion):
        even under replica failures and deadline expiry, every admitted
        request gets a definite answer."""
        return (self.admitted - len(self.ok_responses())
                - self.failed_admitted)

    def latency_summary(self) -> dict:
        lat = np.asarray([r.latency for r in self.ok_responses()])
        if lat.size == 0:
            return {"served": 0, "p50_ms": float("nan"),
                    "p99_ms": float("nan")}
        return {"served": int(lat.size),
                "p50_ms": float(np.percentile(lat, 50) * 1e3),
                "p99_ms": float(np.percentile(lat, 99) * 1e3)}

    def stats(self) -> dict:
        return {
            "epoch": self.epoch,
            "fingerprint": self.fingerprint,
            "submitted": self.submitted,
            "admitted": self.admitted,
            "served": self.served,
            "dropped": self.dropped(),
            "queue_depth": len(self._queue),
            "batches": self.batches,
            "swaps": self.swaps,
            "rejections": dict(self.rejections),
            "cache": {"hits": self.cache.hits, "misses": self.cache.misses,
                      "evictions": self.cache.evictions,
                      "collisions": self.cache.collisions,
                      "size": len(self.cache)},
            "faults": {"retries": self.retries,
                       "replica_failures": self.replica_failures,
                       "breaker_opens": sum(h.opens for h in self.health),
                       "shed": self.shed,
                       "deadline_expired": self.deadline_expired,
                       "failed_admitted": self.failed_admitted},
            "replicas": [{"state": h.state,
                          "failures": h.failures,
                          "successes": h.successes,
                          "opens": h.opens,
                          "consecutive_failures": h.consecutive_failures}
                         for h in self.health],
        }


__all__ = ["Clock", "WallClock", "VirtualClock", "QueryCache", "Response",
           "ReplicaHealth", "ServingScheduler", "bucket_size",
           "canonical_tokens", "multiset_digest", "request_draws",
           "reference_theta",
           "REJECT_QUEUE_FULL", "REJECT_EMPTY", "REJECT_TOO_LONG",
           "REJECT_BAD_WORD", "REJECT_SHED", "REJECT_DEADLINE",
           "REJECT_REPLICA",
           "BREAKER_CLOSED", "BREAKER_OPEN", "BREAKER_HALF_OPEN"]
