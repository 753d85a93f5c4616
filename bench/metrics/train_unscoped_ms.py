"""Device ms per iteration in ops that carry no JAX name stack (ops the
compiler made, such as layout copies and sorts), less any part that an
op with a name stack covers."""
from harness.spans import unscoped_s


def read(ctx):
    t = unscoped_s(ctx)
    n = ctx.counts.get("iterations", 0)
    if t is None or n <= 0:
        return None
    return 1e3 * t / n
