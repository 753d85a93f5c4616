"""The readers of the program's spans, scopes and counters on a hand-made
trace: clipping to the ``bench.window`` span, ``None`` where the program
has nothing to read (as in a parent that lacks the tracing), and the
nesting rule of ``train_unscoped_ms``."""
import pytest

from harness import common, readers, xplane

MS = 1e6                                  # ns


def _op(s, e, scope=""):
    return xplane.Op("%op = f32[] op()", s * MS, e * MS, scope, "")


def _ctx(ops=(), host=(), batch_log=(), counts=None, lo=100, hi=200):
    plane = "/device:TPU:0"
    tr = xplane.Trace(ops={plane: list(ops)},
                      host=[(n, s * MS, e * MS) for n, s, e in host],
                      modules={plane: []})
    return readers.Context(trace=tr, planes=[plane], lo=lo * MS, hi=hi * MS,
                           peaks={}, counts=dict(counts or {}),
                           batch_log=list(batch_log))


def _read(name, ctx):
    return common.load_module("metrics", name).read(ctx)


def test_batch_ms_averages_spans_that_start_in_the_window():
    host = [("serve.batch", 90, 110),      # starts before the window
            ("serve.batch", 120, 130),
            ("serve.batch", 190, 220),     # starts inside, ends after
            ("serve.draws", 120, 121),
            ("serve.batch", 205, 206)]     # after the window
    assert _read("serve_batch_ms.steady", _ctx(host=host)) == \
        pytest.approx((10 + 30) / 2)
    assert _read("serve_batch_ms.steady",
                 _ctx(host=[("bench.tick", 120, 130)])) is None


def test_foldin_wait_clips_to_the_window_per_batch():
    host = [("foldin.fetch", 95, 105), ("foldin.fetch", 150, 160),
            ("foldin.fetch", 195, 230), ("foldin.run", 140, 150)]
    ctx = _ctx(host=host, counts={"batches": 4})
    assert _read("serve_foldin_wait_ms.steady", ctx) == \
        pytest.approx((5 + 10 + 5) / 4)
    assert _read("serve_foldin_wait_ms.steady",
                 _ctx(host=[("foldin.fetch", 10, 20)],
                      counts={"batches": 4})) is None
    assert _read("serve_foldin_wait_ms.steady",
                 _ctx(host=host, counts={"batches": 0})) is None


def test_counters_of_the_batch_log():
    log = [{"size": 2, "wait_s": 0.010, "h2d_bytes": 1_000_000},
           {"size": 6, "wait_s": 0.150, "h2d_bytes": 3_000_000}]
    ctx = _ctx(batch_log=log)
    assert _read("serve_queue_wait_ms.steady", ctx) == \
        pytest.approx(1e3 * 0.160 / 8)
    assert _read("serve_h2d_mb_per_batch.steady", ctx) == pytest.approx(2.0)


def test_counters_read_none_without_the_program_keys():
    # the log a program without the counters writes, and an empty one
    old = [{"size": 2, "epoch": 0, "replica": 0, "t_dispatch": 1.0}]
    for log in (old, []):
        ctx = _ctx(batch_log=log)
        assert _read("serve_queue_wait_ms.steady", ctx) is None
        assert _read("serve_h2d_mb_per_batch.steady", ctx) is None


def test_sample_ms_reads_the_scope_per_iteration():
    ops = [_op(110, 130, "jit(iteration_vmap)/lda.sample/gather:"),
           _op(120, 125, "jit(iteration_vmap)/lda.sample/jit(mh)/x:"),
           _op(190, 230, "jit(iteration_vmap)/lda.sample/scatter:"),
           _op(140, 150, "jit(iteration_vmap)/lda.word_tables/while:")]
    ctx = _ctx(ops=ops, counts={"iterations": 2})
    assert _read("train_sample_ms", ctx) == pytest.approx((20 + 10) / 2)
    assert _read("train_sample_ms", _ctx(ops=ops[3:],
                                         counts={"iterations": 2})) is None


def test_unscoped_ms_leaves_out_what_a_scoped_op_covers():
    ops = [_op(110, 150, "jit(iteration_vmap)/lda.word_tables/while:"),
           _op(120, 130),                  # nested in the scoped while
           _op(140, 160),                  # half out of it
           _op(170, 180),                  # alone
           _op(195, 210)]                  # clipped by the window
    ctx = _ctx(ops=ops, counts={"iterations": 2})
    assert _read("train_unscoped_ms", ctx) == pytest.approx((10 + 10 + 5) / 2)
    scoped = [_op(110, 150, "jit(iteration_vmap)/lda.sample/x:")]
    assert _read("train_unscoped_ms",
                 _ctx(ops=scoped, counts={"iterations": 1})) is None
    # unscoped ops all covered read zero, not nothing
    assert _read("train_unscoped_ms",
                 _ctx(ops=ops[:2], counts={"iterations": 1})) == 0
