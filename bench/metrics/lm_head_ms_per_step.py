"""Model step: device time of the decode programs' operations scoped
``lm_head`` (final norm, the head's product over the vocabulary, argmax),
per decode step in the window, in ms.

Scopes come from the compiled executables that ran
(``bench.program_trace``); a program without named scopes, or a map that
covers under 95 % of the decode programs' device time, reads nothing."""

from bench import program_trace as pt


def read(ctx):
    return pt.decode_scoped_ms_per_step(ctx, ("lm_head",))
