"""Model step: device time of the decode programs (one paged step, and the
k-step scan) per decode step they ran in the window, in ms.

The programs are found by their XLA module names below; a renamed program
reads nothing, and the metric is left out."""

from bench import trace as tr

PROGRAMS = ("jit_paged_decode1", "jit_run")


def read(ctx):
    steps = sum(c.k for c in ctx.calls.decode)
    ns = tr.module_ns(ctx.trace, PROGRAMS, ctx.window_ns)
    if not steps or not ns:
        return None
    return ns * 1e-6 / steps
