"""Whole step on the device: model FLOPs done in the window over served
time times the chip's bf16 peak, in percent.  FLOPs count real prompt
tokens and live decode rows only, with attention over the live context
(``bench.costs``); served time is the time in which a request was queued
or in flight."""

from bench import costs


def read(ctx):
    if not ctx.served_s:
        return None
    flops = sum(costs.prefill_flops(ctx.cfg, n)
                for _, lengths in ctx.calls.prefill for n in lengths)
    flops += sum(costs.decode_flops(ctx.cfg, [d + i for d in depths])
                 for _, k, depths, _ in ctx.calls.decode for i in range(k))
    if not flops:
        return None
    return 100.0 * flops / (ctx.served_s * ctx.peaks["bf16_flops"])
