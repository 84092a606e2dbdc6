"""KV cache: mean of the page pool's ``occupancy`` (claimed pages over all
pages), sampled at every decode step in the window, in percent."""


def read(ctx):
    steps = sum(c.k for c in ctx.calls.decode)
    if not steps:
        return None
    return 100.0 * sum(c.k * c.occupancy for c in ctx.calls.decode) / steps
