"""Model step: device time of the prefill programs per thousand real
(unpadded) prompt tokens prefilled in the window.

The programs are found by their XLA module names below; a renamed program
reads nothing, and the metric is left out."""

from bench import trace as tr

PROGRAMS = ("jit_paged_group_prefill",)


def read(ctx):
    tokens = sum(sum(lengths) for _, lengths in ctx.calls.prefill)
    ns = tr.module_ns(ctx.trace, PROGRAMS, ctx.window_ns)
    if not tokens or not ns:
        return None
    return ns * 1e-6 / (tokens / 1000.0)
