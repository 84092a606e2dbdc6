"""Whole step on the device: model FLOPs done in the window over served
time times the chip's bf16 peak, in percent.  FLOPs count real prompt
tokens and live decode rows only, with attention over the live context
(the configuration's family's counts, ``bench.family``); served time is
the time in which a request was queued or in flight."""

from bench import family


def read(ctx):
    if not ctx.served_s:
        return None
    fam = family.load(ctx.cell.spec)
    flops = sum(fam.prefill_flops(ctx.cfg, n)
                for _, lengths in ctx.calls.prefill for n in lengths)
    flops += sum(fam.decode_flops(ctx.cfg, call) for call in ctx.calls.decode)
    if not flops:
        return None
    return 100.0 * flops / (ctx.served_s * ctx.peaks["bf16_flops"])
