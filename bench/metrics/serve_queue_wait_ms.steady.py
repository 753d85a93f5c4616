"""Mean time a request waited in the scheduler's queue, from its stamped
arrival to its batch's dispatch: the window's ``batch_log`` sum of
``wait_s`` over the sum of ``size``, in ms."""
from harness.spans import log_sum


def read(ctx):
    wait, size = log_sum(ctx, "wait_s"), log_sum(ctx, "size")
    if wait is None or not size:
        return None
    return 1e3 * wait / size
