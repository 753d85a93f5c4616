"""Mean host wall time of a dispatched batch: the ``serve.batch`` spans
(``ServingScheduler._run_batch``) that start in the window, in ms."""
from harness.spans import mean_span_ms

SPAN = "serve.batch"


def read(ctx):
    return mean_span_ms(ctx, SPAN)
