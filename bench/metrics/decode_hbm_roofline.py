"""Model step, as its roofline: the least time the decode steps of the
window could take at the chip's HBM peak, over their device time, in
percent.  The bytes a step needs are every weight once, each live row's
K/V up to its depth and the new K/V row (the configuration's family's
counts, ``bench.family``); decode is bound by them, not by FLOPs, at these
batch sizes."""

from bench import family, trace as tr
from bench.metrics.decode_step_ms import PROGRAMS


def read(ctx):
    ns = tr.module_ns(ctx.trace, PROGRAMS, ctx.window_ns)
    if not ctx.calls.decode or not ns:
        return None
    fam = family.load(ctx.cell.spec)
    need = sum(fam.decode_step_bytes(ctx.cfg, call)
               for call in ctx.calls.decode)
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / (ns * 1e-9)
