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
``decode_step_ms`` and ``host_idle_ms_per_step``.  The readings go
through the harness's own ``traced_context``, with the scope map built
from the same executables' texts as in a traced benchmark run.  No
correctness check: the benchmark's own run makes it.
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

    from bench import cells, harness
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

    window_calls = calls.window(t0, t1)
    texts = pt.decode_texts(adapter, engine,
                            {c.k for c in window_calls.decode})
    ctx = harness.traced_context(cell, cfg, jax.devices()[0].device_kind,
                                 args.out, t0, loop.served, window_calls,
                                 texts)
    trace, served = ctx.trace, ctx.served_ns
    scopes = harness.device_scopes(ctx)
    result = {
        "workload": cell.name, "seed": args.seed,
        "host_us_per_step": {"unprofiled": off_us, "profiled": on_us},
        "steps": {"unprofiled": off_n, "profiled": on_n},
        "serve_spans": len(ctx.spans),
        "scope_coverage": pt.coverage(scopes),
        "device_scopes": sorted(([k, v] for k, v in scopes.items()),
                                key=lambda kv: -kv[1]),
        "idle_gaps_program": [list(x) for x in pt.gaps_by_program(
            trace, ctx.spans, served)],
        "idle_gaps": [list(x) for x in tr.gaps_by_host(trace, served)],
        "metrics": {name: harness.read_metric(name, ctx) for name in (
            "kv_cache_ms_per_step", "lm_head_ms_per_step",
            "decode_step_ms", "device_idle_share", "host_idle_ms_per_step")},
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
