"""Device: the share of served time (a request queued or in flight) in
which no operation ran on the chip, in percent."""

from bench import trace as tr


def read(ctx):
    if not ctx.served_s or not ctx.trace.ops:
        return None
    busy = tr.busy_ns(ctx.trace, ctx.served_ns) * 1e-9
    return 100.0 * (1.0 - busy / ctx.served_s)
