"""Engine loop (host): device-idle time inside served time (a request
queued or in flight) per engine step, in ms: how long the chip waits, each
step, on the host's scheduling, page maps, token copies and streaming.

Steps are the program's own ``serve.step`` spans that start in the traced
window (``Context.spans``); a program without them reads nothing."""

from bench import program_trace as pt


def read(ctx):
    return pt.host_idle_ms_per_step(ctx.trace, ctx.spans, ctx.served_ns)
