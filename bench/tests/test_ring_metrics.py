"""The ring cell's readers on a hand-made four-chip trace: per-chip
averages over the planes, asynchronous permutes counted from start to
done for the link's share, the padding share from the engine's counters,
and ``None`` where there is nothing to read."""
import pytest

from harness import collectives, common, readers, xplane

MS = 1e6                                  # ns
ROT = "jit(per_device)/shard_map/while/body/closed_call/lda.rotate/ppermute"
SYNC = "jit(per_device)/shard_map/while/body/closed_call/lda.ck_sync/psum"
SAMPLE = "jit(per_device)/shard_map/while/body/closed_call/lda.sample/x"
PLANES = [f"/device:TPU:{i}" for i in range(4)]
PEAKS = {"hbm_bytes_per_s": 819e9, "ici_bits_per_s": 1600e9}
GEOMETRY = {"vocab_size": 141043, "num_topics": 1000, "num_workers": 4,
            "blocks_per_worker": 2}

START = ("%collective-permute-start.2 = (s32[3,17631,1000]{1,2,0:T(8,128)}"
         ", s32[3,17631,1000]{1,2,0:T(8,128)}, u32[]{:S(2)}, u32[]{:S(2)}) "
         "collective-permute-start(%get-tuple-element.2425), channel_id=1")
DONE = ("%collective-permute-done.2 = s32[3,17631,1000]{1,2,0:T(8,128)} "
        "collective-permute-done(%collective-permute-start.2)")


def _op(s, e, scope="", name="%fusion.1 = s32[8]{0} fusion(%p)"):
    return xplane.Op(name, s * MS, e * MS, scope, "")


def _ctx(per_plane, counts=None, lo=100, hi=200):
    tr = xplane.Trace(ops={p: list(ops) for p, ops in zip(PLANES,
                                                          per_plane)},
                      host=[], modules={p: [] for p in PLANES})
    return readers.Context(trace=tr, planes=PLANES, lo=lo * MS, hi=hi * MS,
                           peaks=PEAKS, counts=dict(counts or {}))


def _read(name, ctx):
    return common.load_module("metrics", name).read(ctx)


def test_scope_times_average_over_the_chips():
    planes = [[_op(110, 110 + 4 * (i + 1), ROT), _op(150, 152, SYNC),
               _op(120, 140, SAMPLE)] for i in range(4)]
    ctx = _ctx(planes, counts={"iterations": 2})
    assert _read("ring_rotate_ms", ctx) == pytest.approx((4 + 8 + 12 + 16)
                                                         / 4 / 2)
    assert _read("ring_ck_sync_ms", ctx) == pytest.approx(2 / 2)
    assert _read("ring_sample_ms", ctx) == pytest.approx(20 / 2)
    empty = _ctx([[_op(120, 140, SAMPLE)]] * 4, counts={"iterations": 2})
    assert _read("ring_rotate_ms", empty) is None
    assert _read("ring_ck_sync_ms", empty) is None


def test_idle_share_averages_the_planes():
    planes = [[_op(100, 150)], [_op(100, 200)], [], [_op(150, 200)]]
    assert _read("ring_idle_share", _ctx(planes)) == pytest.approx(50.0)


def test_in_flight_runs_from_start_to_done():
    # the permute is in flight 110..160 while a sample op runs; its own
    # ops cover 110..111 and 159..160
    ops = [_op(110, 111, ROT, START), _op(115, 150, SAMPLE),
           _op(159, 160, ROT, DONE), _op(170, 175, ROT)]
    ctx = _ctx([ops] * 4, counts={"iterations": 1})
    assert collectives.in_flight_s(ctx, ("lda.rotate",)) == \
        pytest.approx((50 + 5) / 1e3)
    assert _read("ring_rotate_ms", ctx) == pytest.approx(1 + 1 + 5)
    # a start with no done in the trace counts its own op only
    alone = _ctx([[_op(110, 111, ROT, START)]] * 4)
    assert collectives.in_flight_s(alone, ("lda.rotate",)) == \
        pytest.approx(1 / 1e3)
    # events named by their instruction alone pair by that name
    bare = [_op(110, 111, ROT, "collective-permute-start.2"),
            _op(159, 160, ROT, "collective-permute-done.2")]
    assert collectives.in_flight_s(_ctx([bare] * 4), ("lda.rotate",)) == \
        pytest.approx(50 / 1e3)


def test_link_share_of_the_rotation():
    ops = [_op(110, 111, ROT, START), _op(159, 160, ROT, DONE)]
    counts = dict(GEOMETRY, iterations=2)
    ctx = _ctx([ops] * 4, counts=counts)
    vb = -(-141043 // 8)
    need = 2 * 8 * (4 * vb * 1000 * 4 + 4)             # about 4.51 GB
    assert need == common.load_module(
        "roofline", "ring_rotate").bytes_moved(counts)
    assert _read("ring_rotate_ici_roofline", ctx) == pytest.approx(
        100 * 8 * need / 1600e9 / 0.050)
    assert _read("ring_rotate_ici_roofline",
                 _ctx([[]] * 4, counts=counts)) is None


def test_pad_share_from_the_counters():
    ctx = _ctx([[]] * 4, counts={"slots": 800, "real_tokens": 100})
    assert _read("ring_pad_share", ctx) == pytest.approx(87.5)
    # a program without the counters
    assert _read("ring_pad_share", _ctx([[]] * 4,
                                        counts={"tokens": 100})) is None


def test_mfu_is_one_chips_share_of_the_bytes():
    counts = {"tokens": 4_000_000, "iterations": 1}
    ctx = _ctx([[]] * 4, counts=counts)
    assert _read("ring_mfu", ctx) == pytest.approx(
        100 * 248 * 4_000_000 / 4 / 819e9 / 0.1)
    assert _read("ring_mfu", _ctx([[]] * 4, counts={"tokens": 0})) is None
