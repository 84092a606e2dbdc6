"""Profile one cell at its full size: the program's own spans and scopes,
and what a profiler session costs the engine's host loop.

    python3 bench/profile_cell.py --workload granite_8b.chat-poisson \
        --seed 7 --seconds 20 --out /tmp/profile

After the same set-up as ``bench/run.py``, offers the cell's traffic twice
for ``--seconds``, each stretch drained before the next: first with no
profiler session, then inside one, timing every ``engine.step()`` call
on the host.  Keeps the profiler's trace under ``--out`` and prints one
JSON line: the median host microseconds per step in each stretch; of the
profiled stretch, the decode programs' device seconds by named scope and
the scope map's coverage, idle device time by program span (and by the
harness's spans), and ``kv_cache_ms_per_step``, ``lm_head_ms_per_step``,
``decode_step_ms`` and ``host_idle_ms_per_step``.  No correctness check:
the benchmark's own run makes it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import cells, harness, peaks
    from bench import program_trace as pt
    from bench import trace as tr
    cell = cells.load_cell(args.workload, ROOT)
    harness.use_compile_cache()
    cfg, adapter, engine = harness.build(cell, args.seed)
    harness.warm_up(cell, adapter, engine)
    calls = harness.Calls()
    span = harness.instrument(engine, adapter, calls, traced=True)
    step_s = []
    step = engine.step

    def timed_step():
        t = time.perf_counter()
        out = step()
        step_s.append(time.perf_counter() - t)
        return out
    engine.step = timed_step

    def stretch(window):
        """Offer the traffic inside ``window``, then drain; returns the
        loop, the window's times and the median host us per step."""
        loop = harness.Loop(engine, span, cell)
        del step_s[:]
        with window:
            t0 = time.perf_counter()
            harness.drive(loop, cell, args.seed, args.seconds,
                          cfg.vocab_size, t0)
            jax.block_until_ready(engine.cache)
            t1 = time.perf_counter()
        median_us = statistics.median(step_s) * 1e6
        n = len(step_s)
        loop.drain(time.perf_counter() + harness.DRAIN_S)
        return loop, t0, t1, median_us, n

    _, _, _, off_us, off_n = stretch(span("unprofiled"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(args.out, profiler_options=opts)
    loop, t0, t1, on_us, on_n = stretch(span("window"))
    jax.profiler.stop_trace()

    trace = tr.load(args.out)
    spans = pt.load_spans(args.out)
    lo, hi = trace.window
    to_ns = lambda t: lo + (t - t0) * 1e9
    served = tr.Clip((max(to_ns(a), lo), min(to_ns(b), hi))
                     for a, b in loop.served
                     if to_ns(b) > lo and to_ns(a) < hi).iv
    window_calls = calls.window(t0, t1)
    kind = jax.devices()[0].device_kind
    ctx = harness.Context(cell, cfg, peaks.peaks(kind), window_calls, trace,
                          [trace.window], served,
                          sum(b - a for a, b in served) * 1e-9)
    ks = {k for _, k, _, _ in window_calls.decode}
    ctx.scope_map = pt.scope_map(pt.decode_hlo_texts(cell, cfg, ks))
    by_scope = pt.scope_ns(trace, pt.DECODE_PROGRAMS, [trace.window],
                           ctx.scope_map)
    result = {
        "workload": cell.name, "seed": args.seed,
        "host_us_per_step": {"unprofiled": off_us, "profiled": on_us},
        "steps": {"unprofiled": off_n, "profiled": on_n},
        "serve_spans": len(spans),
        "scope_coverage": pt.coverage(by_scope),
        "device_scopes": sorted(
            ([k, v * 1e-9] for k, v in by_scope.items()),
            key=lambda kv: -kv[1]),
        "idle_gaps_program": [list(x) for x in pt.gaps_by_program(
            trace, spans, served)],
        "idle_gaps": [list(x) for x in tr.gaps_by_host(trace, served)],
        "metrics": {name: harness.read_metric(name, ctx) for name in (
            "kv_cache_ms_per_step", "lm_head_ms_per_step",
            "decode_step_ms", "device_idle_share")},
    }
    result["metrics"]["host_idle_ms_per_step"] = pt.host_idle_ms_per_step(
        trace, spans, served)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
