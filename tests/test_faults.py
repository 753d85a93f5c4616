"""Deterministic fault injection + crash-recovery supervisor
(DESIGN.md §15).

The headline property pinned here, for BOTH engines: a training chain
killed by injected crashes at several distinct step offsets and
auto-restarted by the :class:`~repro.launch.supervise.Supervisor` ends
**bitwise equal** — every count array, every assignment, and the rng
bit-generator state — to the chain that never crashed.  Plus: a kill at
EVERY fire point inside both engines' ``save_checkpoint`` leaves a
workdir the quarantine pass turns back into a resumable (old or new,
never mixed) checkpoint, and the atomic-JSON writer survives a kill
between temp-write and rename.
"""
import contextlib
import json
import os

import numpy as np
import pytest

from repro.core import faults
from repro.core.faults import FaultPlan, FaultSpec, InjectedCrash
from repro.data import integrity
from repro.launch import supervise
from repro.launch.supervise import (RestartBudgetExceeded, Supervisor,
                                    prepare_restart)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """A crash mid-test must never leave a plan armed for the next."""
    yield
    faults.deactivate()


def _plan_ctx(plan):
    return faults.injected(plan) if plan is not None \
        else contextlib.nullcontext()


# ---------------------------------------------------------------------------
# FaultPlan mechanics
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_noop_without_plan(self):
        faults.fire("step", "iter:0,engine:streaming")  # must not raise
        assert faults.delay("replica", "replica:0,epoch:1") == 0.0

    def test_crash_at_step_matches_exact_iteration(self):
        plan = FaultPlan.crash_at_step(1)
        with faults.injected(plan):
            faults.fire("step", "iter:0,engine:streaming")
            faults.fire("step", "iter:10,engine:streaming")  # no prefix hit
            with pytest.raises(InjectedCrash):
                faults.fire("step", "iter:1,engine:streaming")
        assert plan.fired and "iter:1," in plan.fired[0]

    def test_nth_occurrence_and_every(self):
        plan = FaultPlan([FaultSpec("io_error", "read", "", nth=2)])
        plan.fire("read", "a")                       # 1st: no fire
        with pytest.raises(faults.InjectedIOError):
            plan.fire("read", "b")                   # 2nd: fires
        plan.fire("read", "c")                       # 3rd: spent
        every = FaultPlan([FaultSpec("io_error", "read", "", nth=0)])
        for d in ("a", "b", "c"):
            with pytest.raises(faults.InjectedIOError):
                every.fire("read", d)

    def test_json_roundtrip_resets_counters(self):
        plan = FaultPlan([FaultSpec("crash", "step", "iter:2,", 1)], seed=7)
        with pytest.raises(InjectedCrash):
            plan.fire("step", "iter:2,")
        clone = FaultPlan.from_json(plan.to_json())
        assert clone.seed == 7 and clone.specs == plan.specs
        with pytest.raises(InjectedCrash):         # fresh counters
            clone.fire("step", "iter:2,")

    def test_from_json_rejects_other_formats(self):
        with pytest.raises(ValueError):
            FaultPlan.from_json('{"format": "something-else"}')

    def test_env_var_pickup(self, monkeypatch):
        plan = FaultPlan.crash_at_point("write", match="x.npy")
        monkeypatch.setenv(faults.ENV_VAR, plan.to_json())
        monkeypatch.setattr(faults, "_active", None)
        monkeypatch.setattr(faults, "_env_checked", False)
        got = faults.active()
        assert got is not None and got.specs == plan.specs
        faults.deactivate()

    def test_bit_flip_kind_corrupts_artifact(self, tmp_path):
        p = str(tmp_path / "a.npy")
        integrity.save_npy(p, np.arange(64))
        plan = FaultPlan([FaultSpec("bit_flip", "read", "a.npy", nth=1,
                                    arg=-1.0)])
        with faults.injected(plan):
            with pytest.raises(integrity.CorruptArtifactError):
                integrity.load_npy(p)  # fire("read") flips, checksum trips

    def test_replica_slow_delay_accumulates(self):
        plan = FaultPlan.replica_slow(1, 0.25, nth=0)
        assert plan.delay("replica", "replica:0,epoch:1") == 0.0
        assert plan.delay("replica", "replica:1,epoch:1") == 0.25
        assert plan.delay("replica", "replica:1,epoch:2") == 0.25

    def test_injected_ctx_disarms_even_on_crash(self):
        with pytest.raises(InjectedCrash):
            with faults.injected(FaultPlan.crash_at_point("step")):
                faults.fire("step", "anything")
        assert faults.active() is None


# ---------------------------------------------------------------------------
# Atomic JSON writes: kill between temp-write and rename (satellite)
# ---------------------------------------------------------------------------

class TestAtomicJsonCrash:
    def test_old_content_survives_kill_before_rename(self, tmp_path):
        p = str(tmp_path / "manifest.json")
        integrity.atomic_write_json(p, {"v": 1}, checksum=True)
        with faults.injected(FaultPlan.crash_at_point("json.tmp_written")):
            with pytest.raises(InjectedCrash):
                integrity.atomic_write_json(p, {"v": 2}, checksum=True)
        with open(p) as f:
            assert json.load(f) == {"v": 1}      # old content intact
        assert integrity.validate_file(p) is True  # sidecar still matches

    def test_no_file_at_all_if_first_write_killed(self, tmp_path):
        p = str(tmp_path / "fresh.json")
        with faults.injected(FaultPlan.crash_at_point("json.tmp_written")):
            with pytest.raises(InjectedCrash):
                integrity.atomic_write_json(p, {"v": 1})
        assert not os.path.exists(p)             # never half-written


# ---------------------------------------------------------------------------
# Shared fixtures / helpers for the engine-level tests
# ---------------------------------------------------------------------------

TOTAL_ITERS = 4
K, W, SEED = 4, 2, 5


def _stream_corpus(tmp_path):
    from repro.data.stream import write_zipf_stream
    return write_zipf_stream(str(tmp_path / "corpus"), 18, 48, 9,
                             seed=11, docs_per_shard=6)


def _stream_chain_state(lda):
    s = lda.gather_counts()
    return (np.asarray(s.cdk), np.asarray(s.ckt), np.asarray(s.ck),
            lda.assignments(), lda._rng.bit_generator.state,
            lda.iteration_count)


def _assert_state_equal(a, b, ctx):
    for name, x, y in zip(("cdk", "ckt", "ck", "z"), a[:4], b[:4]):
        np.testing.assert_array_equal(x, y,
                                      err_msg=f"{ctx}: {name} diverged")
    assert a[4] == b[4], f"{ctx}: rng state diverged"
    assert a[5] == b[5], f"{ctx}: iteration count diverged"


def _stream_reference(tmp_path, cdir):
    from repro.core.engine.streaming import StreamingLDA
    lda = StreamingLDA(cdir, str(tmp_path / "wd_ref"), K, W, seed=SEED)
    lda.run(TOTAL_ITERS, checkpoint_every=1)
    return _stream_chain_state(lda)


def _mp_corpus():
    from repro.data.synthetic import synthetic_corpus
    corpus, _, _ = synthetic_corpus(16, 32, K, 8, seed=3)
    return corpus


def _mp_chain_state(lda):
    s = lda.gather_counts()
    return (np.asarray(s.cdk), np.asarray(s.ckt), np.asarray(s.ck),
            lda.assignments(), lda._rng.bit_generator.state,
            lda.iteration_count)


# ---------------------------------------------------------------------------
# Kill during save_checkpoint, at EVERY fire point, both engines
# ---------------------------------------------------------------------------

class TestCheckpointKillStreaming:
    @pytest.mark.parametrize("point", ["ckpt.begin", "ckpt.tmp_copied",
                                       "ckpt.old_moved", "ckpt.promoted"])
    def test_kill_mid_checkpoint_resumes_consistent(self, tmp_path, point):
        """Kill inside the checkpoint's atomic swap: resume must land on
        the OLD checkpoint or the NEW one — never a mix — and continuing
        to TOTAL_ITERS matches the uninterrupted chain bitwise."""
        from repro.core.engine.streaming import StreamingLDA
        cdir = _stream_corpus(tmp_path)
        ref = _stream_reference(tmp_path, cdir)

        wd = str(tmp_path / "wd_kill")
        lda = StreamingLDA(cdir, wd, K, W, seed=SEED)
        lda.run(2, checkpoint_every=1)           # good checkpoint @ iter 2
        lda.step()                               # iter 3, not yet saved
        with faults.injected(FaultPlan.crash_at_point(point)):
            with pytest.raises(InjectedCrash):
                lda.save_checkpoint()

        info = prepare_restart(wd)
        assert info["kind"] == "streaming" and info["resumable"]
        res = StreamingLDA.resume(wd)
        assert res.iteration_count in (2, 3), \
            f"kill at {point}: landed on mixed iteration"
        while res.iteration_count < TOTAL_ITERS:
            res.step()
            res.save_checkpoint()
        _assert_state_equal(_stream_chain_state(res), ref,
                            f"kill at {point}")

    def test_second_checkpoint_after_promote_kill(self, tmp_path):
        """A kill right after promote leaves ckpt.old behind; the NEXT
        save_checkpoint must clear the debris, not trip over it."""
        from repro.core.engine.streaming import StreamingLDA
        cdir = _stream_corpus(tmp_path)
        wd = str(tmp_path / "wd")
        lda = StreamingLDA(cdir, wd, K, W, seed=SEED)
        lda.run(2, checkpoint_every=1)
        lda.step()
        with faults.injected(FaultPlan.crash_at_point("ckpt.promoted")):
            with pytest.raises(InjectedCrash):
                lda.save_checkpoint()
        assert os.path.isdir(os.path.join(wd, "ckpt.old"))
        lda.save_checkpoint()                    # in-process retry works
        assert not os.path.exists(os.path.join(wd, "ckpt.old"))
        assert StreamingLDA.resume(wd).iteration_count == 3


class TestCheckpointKillMP:
    @pytest.mark.parametrize("point,match", [
        ("mp_ckpt.begin", ""), ("npz.tmp_written", "engine_ckpt"),
        ("mp_ckpt.promoted", "")])
    def test_kill_mid_checkpoint_resumes_consistent(self, tmp_path, point,
                                                    match):
        from repro.core.engine import ModelParallelLDA
        corpus = _mp_corpus()
        ref = ModelParallelLDA(corpus, K, W, seed=SEED)
        for _ in range(TOTAL_ITERS):
            ref.step()
        ref_state = _mp_chain_state(ref)

        wd = str(tmp_path / "wd")
        os.makedirs(wd)
        ckpt = os.path.join(wd, supervise.MP_CKPT)
        lda = ModelParallelLDA(corpus, K, W, seed=SEED)
        lda.step()
        lda.step()
        lda.save_checkpoint(ckpt)                # good checkpoint @ iter 2
        lda.step()                               # iter 3
        with faults.injected(FaultPlan.crash_at_point(point, match=match)):
            with pytest.raises(InjectedCrash):
                lda.save_checkpoint(ckpt)

        info = prepare_restart(wd)
        assert info["kind"] == "mp" and info["resumable"]
        assert not os.path.exists(ckpt + ".tmp")  # debris quarantined
        res = ModelParallelLDA.resume(corpus, ckpt)
        assert res.iteration_count in (2, 3), \
            f"kill at {point}: landed on mixed iteration"
        while res.iteration_count < TOTAL_ITERS:
            res.step()
        _assert_state_equal(_mp_chain_state(res), ref_state,
                            f"kill at {point}")


# ---------------------------------------------------------------------------
# Supervisor: quarantine + restart decisions
# ---------------------------------------------------------------------------

class TestPrepareRestart:
    def test_empty_and_missing_workdir(self, tmp_path):
        assert prepare_restart(str(tmp_path / "nope"))["kind"] is None
        wd = str(tmp_path / "wd")
        os.makedirs(wd)
        info = prepare_restart(wd)
        assert info == {"kind": None, "resumable": False, "quarantined": []}

    def test_clean_streaming_workdir_untouched(self, tmp_path):
        from repro.core.engine.streaming import StreamingLDA
        cdir = _stream_corpus(tmp_path)
        wd = str(tmp_path / "wd")
        StreamingLDA(cdir, wd, K, W, seed=SEED).run(1, checkpoint_every=1)
        info = prepare_restart(wd)
        assert info["kind"] == "streaming" and info["resumable"]
        assert info["quarantined"] == []
        # idempotent
        assert prepare_restart(wd)["quarantined"] == []

    def test_corrupt_streaming_ckpt_quarantined_not_deleted(self, tmp_path):
        from repro.core.engine.streaming import StreamingLDA
        cdir = _stream_corpus(tmp_path)
        wd = str(tmp_path / "wd")
        StreamingLDA(cdir, wd, K, W, seed=SEED).run(1, checkpoint_every=1)
        integrity.flip_byte(os.path.join(wd, "ckpt", "ck.npy"), seed=1)
        info = prepare_restart(wd)
        assert info["kind"] == "streaming" and not info["resumable"]
        qroot = os.path.join(wd, supervise.QUARANTINE_DIR)
        assert os.path.isdir(qroot) and len(os.listdir(qroot)) > 0
        assert any("ckpt" in os.path.basename(q)
                   for q in info["quarantined"])
        # nothing but the quarantine dir remains: next attempt is fresh
        assert sorted(os.listdir(wd)) == [supervise.QUARANTINE_DIR]

    def test_mp_tmp_debris_quarantined(self, tmp_path):
        from repro.core.engine import ModelParallelLDA
        corpus = _mp_corpus()
        wd = str(tmp_path / "wd")
        os.makedirs(wd)
        ckpt = os.path.join(wd, supervise.MP_CKPT)
        lda = ModelParallelLDA(corpus, K, W, seed=SEED)
        lda.step()
        lda.save_checkpoint(ckpt)
        with open(ckpt + ".tmp", "wb") as f:
            f.write(b"half a checkpoint")
        info = prepare_restart(wd)
        assert info["kind"] == "mp" and info["resumable"]
        assert len(info["quarantined"]) == 1
        assert not os.path.exists(ckpt + ".tmp")
        ModelParallelLDA.resume(corpus, ckpt)    # survivor still loads

    def test_backoff_is_deterministic_and_bounded(self, tmp_path):
        mk = lambda seed: Supervisor(lambda a, r: 0, str(tmp_path),
                                     seed=seed, backoff_base=0.05,
                                     backoff_cap=2.0)
        a, b, c = mk(1), mk(1), mk(2)
        for i in range(6):
            assert a.backoff(i) == b.backoff(i)
            assert 0.0 < a.backoff(i) <= 2.0 * 1.5
        assert any(a.backoff(i) != c.backoff(i) for i in range(6))

    def test_restart_budget_exceeded(self, tmp_path):
        sleeps = []

        def always_crash(attempt, resumable):
            raise RuntimeError(f"boom {attempt}")

        sup = Supervisor(always_crash, str(tmp_path), max_restarts=2,
                         sleep=sleeps.append, log=lambda m: None)
        with pytest.raises(RestartBudgetExceeded):
            sup.run()
        assert len(sleeps) == 2                  # one backoff per restart

    def test_injected_crash_is_caught_by_supervisor(self, tmp_path):
        calls = []

        def child(attempt, resumable):
            calls.append(attempt)
            if attempt == 0:
                raise InjectedCrash("step", "iter:0,", 0)
            return 0

        rep = Supervisor(child, str(tmp_path), sleep=lambda d: None,
                         log=lambda m: None).run()
        assert calls == [0, 1] and rep.exit_code == 0 and rep.restarts == 1
        assert rep.crashes and "InjectedCrash" in rep.crashes[0]

    def test_strip_supervise_args(self):
        argv = ["--engine", "mp", "--supervise", "--max-restarts", "5",
                "--restart-backoff=0.1", "--iters", "3"]
        assert supervise.strip_supervise_args(argv) == \
            ["--engine", "mp", "--iters", "3"]


# ---------------------------------------------------------------------------
# The headline property: crashed+supervised == uninterrupted, bitwise
# ---------------------------------------------------------------------------

CRASH_OFFSETS = [0, 2, 3]      # >= 3 distinct step offsets (acceptance)


def _make_supervised_child(plans, build, resume, total=TOTAL_ITERS):
    """In-process lda_train analogue: attempt i runs under plans[i]
    (None = no faults), building fresh or resuming per the supervisor's
    quarantine verdict, checkpointing every iteration."""

    def run_child(attempt, resumable):
        plan = plans[attempt] if attempt < len(plans) else None
        with _plan_ctx(plan):
            lda = resume() if resumable else build()
            while lda.iteration_count < total:
                lda.step()
                lda.checkpoint()
        return 0

    return run_child


class TestSupervisedBitwiseRecovery:
    def test_streaming_crashes_at_three_offsets(self, tmp_path):
        from repro.core.engine.streaming import StreamingLDA
        cdir = _stream_corpus(tmp_path)
        ref = _stream_reference(tmp_path, cdir)

        wd = str(tmp_path / "wd_crash")
        plans = [FaultPlan.crash_at_step(n) for n in CRASH_OFFSETS]

        def wrap(lda):
            lda.checkpoint = lda.save_checkpoint
            return lda

        child = _make_supervised_child(
            plans,
            build=lambda: wrap(StreamingLDA(cdir, wd, K, W, seed=SEED)),
            resume=lambda: wrap(StreamingLDA.resume(wd)))
        rep = Supervisor(child, wd, max_restarts=len(plans),
                         sleep=lambda d: None, log=lambda m: None).run()
        assert rep.exit_code == 0
        assert rep.restarts == len(CRASH_OFFSETS)
        # crash at iter 0 precedes any checkpoint -> fresh; later crashes
        # resume from the last good checkpoint
        assert rep.resumed == [False, False, True, True]
        assert rep.quarantined                  # iter-0 debris quarantined

        final = StreamingLDA.resume(wd)
        _assert_state_equal(_stream_chain_state(final), ref,
                            "supervised streaming recovery")

    @pytest.mark.parametrize("backend", ["vmap", "shard_map"])
    def test_mp_engine_both_backends(self, tmp_path, backend):
        from repro.core.engine import ModelParallelLDA
        corpus = _mp_corpus()
        ref = ModelParallelLDA(corpus, K, W, seed=SEED, backend=backend)
        for _ in range(TOTAL_ITERS):
            ref.step()
        ref_state = _mp_chain_state(ref)

        wd = str(tmp_path / "wd")
        os.makedirs(wd)
        ckpt = os.path.join(wd, supervise.MP_CKPT)
        plans = [FaultPlan.crash_at_step(n) for n in CRASH_OFFSETS]

        def wrap(lda):
            lda.checkpoint = lambda: lda.save_checkpoint(ckpt)
            return lda

        child = _make_supervised_child(
            plans,
            build=lambda: wrap(ModelParallelLDA(corpus, K, W, seed=SEED,
                                                backend=backend)),
            resume=lambda: wrap(ModelParallelLDA.resume(corpus, ckpt,
                                                        backend=backend)))
        rep = Supervisor(child, wd, max_restarts=len(plans),
                         sleep=lambda d: None, log=lambda m: None).run()
        assert rep.exit_code == 0
        assert rep.resumed == [False, False, True, True]

        final = ModelParallelLDA.resume(corpus, ckpt, backend=backend)
        _assert_state_equal(_mp_chain_state(final), ref_state,
                            f"supervised mp recovery [{backend}]")

    def test_crash_mid_checkpoint_then_supervised_recovery(self, tmp_path):
        """Compound failure: the crash lands INSIDE save_checkpoint (the
        torn-swap window), so the supervisor must quarantine the debris
        AND the resumed chain must still match bitwise."""
        from repro.core.engine.streaming import StreamingLDA
        cdir = _stream_corpus(tmp_path)
        ref = _stream_reference(tmp_path, cdir)

        wd = str(tmp_path / "wd")
        plans = [FaultPlan.crash_at_point("ckpt.tmp_copied", nth=2)]

        def wrap(lda):
            lda.checkpoint = lda.save_checkpoint
            return lda

        child = _make_supervised_child(
            plans,
            build=lambda: wrap(StreamingLDA(cdir, wd, K, W, seed=SEED)),
            resume=lambda: wrap(StreamingLDA.resume(wd)))
        rep = Supervisor(child, wd, max_restarts=2, sleep=lambda d: None,
                         log=lambda m: None).run()
        assert rep.exit_code == 0 and rep.restarts == 1
        assert any("ckpt.tmp" in os.path.basename(q)
                   for q in rep.quarantined)
        final = StreamingLDA.resume(wd)
        _assert_state_equal(_stream_chain_state(final), ref,
                            "mid-checkpoint crash recovery")
