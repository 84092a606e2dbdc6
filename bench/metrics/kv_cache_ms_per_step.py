"""KV cache: device time of the decode programs' operations scoped
``page_gather``, ``page_scatter`` or ``kv_write`` (the per-slot view
gathered from the page pool, scattered back, and each new K/V row
written), per decode step in the window, in ms.

Scopes come from the compiled executables that ran
(``bench.program_trace``); a program without named scopes, or a map that
covers under 95 % of the decode programs' device time, reads nothing."""

from bench import program_trace as pt

SCOPES = ("page_gather", "page_scatter", "kv_write")


def read(ctx):
    return pt.decode_scoped_ms_per_step(ctx, SCOPES)
