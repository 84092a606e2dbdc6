"""Scheduler: mean live decode rows over ``n_slots``, per decode step in the
window, in percent (a fused stretch of k steps counts k times)."""


def read(ctx):
    steps = sum(c.k for c in ctx.calls.decode)
    if not steps:
        return None
    rows = sum(c.k * len(c.depths) for c in ctx.calls.decode)
    return 100.0 * rows / (steps * ctx.n_slots)
