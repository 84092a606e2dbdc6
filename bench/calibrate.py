"""Readings that set the benchmark's limits and rates; not run by the cells.

    python3 bench/calibrate.py --workload granite_8b.chat-poisson \
        --seeds 101-112 --seconds 12 [--controls fp8,int8]
    python3 bench/calibrate.py --workload granite_8b.chat-poisson \
        --rates 2,3,4,5,6 --seconds 30 [--arrivals poisson]
    python3 bench/calibrate.py --workload granite_8b.chat-poisson \
        --windows 1-6 --seconds 50 [--arrivals poisson]

``--seeds``: for each seed, weights from the seed, a short window at the
cell's own load, the drain, then the correctness check's numbers for the
served tokens (the lower readings) and, for each control, the numbers of
the tokens the reference one precision lower puts first (the upper
readings).  Each set of numbers goes through the cell's own check
(``correctness.judge`` with the configuration's limits), so the line says
whether the control comes out ``correct``: it must not.  The line also
holds every checked token's gap, in the order of ``chosen``, and the
sizes of every delivered request, so that another number, or the same at
another budget (``--budget-scale``), can be read later.

``--rates``: the knee sweep of an open-loop cell: the same window at each
arrival rate, with the queue's depth over time and the latency tails.

``--windows``: the end-to-end metrics of one window per seed, to read
their spread from seed to seed in one process.

``--arrivals`` replaces the traffic file's open-loop arrivals.  One process
throughout, so the programs compile once.  Each reading is one JSON line
on standard output.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

LATENCIES = ("ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms", "tpot_p90_ms",
             "tpot_p95_ms")


def seeds_arg(text):
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_arg, default=None)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--windows", type=seeds_arg, default=None)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--controls", default="fp8,int8,w8a16")
    ap.add_argument("--budget-scale", type=int, default=1)
    ap.add_argument("--arrivals", default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from bench import cells, correctness, family, harness
    from bench.common import seed_key
    from repro.fleet.replica import build_engine

    cell = cells.load_cell(args.workload, ROOT)
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"calibrate: needs a TPU, found "
                 f"{jax.devices()[0].platform!r}")
    harness.use_compile_cache()
    base = dict(cell.traffic)
    if args.arrivals:
        base["arrivals"] = args.arrivals
    t = time.perf_counter()
    cfg, adapter, engine = harness.build(cell, 0)
    harness.warm_up(cell, adapter, engine)
    harness.free(engine.cache)
    print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
    positions, rows = correctness.budget(cell, args.budget_scale)
    controls = [c for c in args.controls.split(",") if c]
    limits = cell.spec["check"]

    def window(seed, traffic, seconds):
        eng = build_engine(adapter, cell.engine_config())
        loop = harness.Loop(eng, lambda name: contextlib.nullcontext(), cell)
        depth = []
        step = loop.step

        def sampled_step():
            out = step()
            depth.append((time.perf_counter(), len(eng.queue)))
            return out
        loop.step = sampled_step
        c = cells.Cell(cell.name, cell.chips, cell.spec, traffic, [], [])
        stats0 = eng.progress()
        t0 = time.perf_counter()
        harness.drive(loop, c, seed, seconds, cfg.vocab_size, t0)
        jax.block_until_ready(eng.cache)
        t1 = time.perf_counter()
        stats1 = eng.progress()
        tokens = sum(stats1[k] - stats0[k]
                     for k in ("decode_tokens", "prefill_count"))
        loop.drain(t1 + harness.DRAIN_S)
        recs = [r for r in loop.records if r.delivered is not None]
        e2e = {m: harness.end_to_end(m, recs, tokens, t1 - t0, 0.0)
               for m in LATENCIES + ("output_tok_s",)}
        half = [np.mean([d for t, d in depth
                         if f <= 2 * (t - t0) / seconds < f + 1] or [0])
                for f in (0, 1)]
        e2e.update(requests=len(loop.records),
                   delivered_in_window=sum(r.delivered < t1 for r in recs),
                   queue_mean_first_half=half[0],
                   queue_mean_second_half=half[1],
                   drain_s=time.perf_counter() - t1)
        return loop, e2e

    for rate in [float(r) for r in (args.rates or "").split(",") if r]:
        loop, e2e = window(1000 + int(rate * 10),
                           dict(base, rate_per_s=rate), args.seconds)
        print(json.dumps(dict(rate=rate, arrivals=base.get("arrivals"),
                              **e2e)), flush=True)
        harness.free(loop.engine.cache)

    for seed in args.windows or []:
        loop, e2e = window(seed, base, args.seconds)
        print(json.dumps(dict(seed=seed, arrivals=base.get("arrivals"),
                              **e2e)), flush=True)
        harness.free(loop.engine.cache)

    for seed in args.seeds or []:
        t = time.perf_counter()
        harness.free(adapter.params)
        adapter.params = family.load(cell.spec).init_weights(cfg)(
            seed_key(seed))
        loop, e2e = window(seed, base, args.seconds)
        harness.free(adapter.params, loop.engine.cache)
        loop.engine = None
        recs = [r for r in loop.records if r.delivered is not None]
        by_idx = {r.idx: (r.prompt, r.tokens) for r in recs}
        chosen = correctness.sample(by_idx, seed, positions, rows)
        t3 = time.perf_counter()
        g = correctness.gaps(cell.spec, cfg, seed,
                             [by_idx[i][0] for i in chosen],
                             [by_idx[i][1] for i in chosen], positions,
                             rows, controls)
        judged = {}
        for who, gap in g.items():
            values = correctness.numbers(gap)
            checks, ok = correctness.judge(values, limits)
            judged[who] = dict(values, correct=ok)
        print(json.dumps({
            "seed": seed, "requests": len(loop.records),
            "checked": len(chosen), "checked_tokens": len(g["served"]),
            "numbers": judged, "limits": limits,
            "reference_s": time.perf_counter() - t3,
            "drain_s": e2e["drain_s"], "seed_s": time.perf_counter() - t,
            "sizes": {i: [len(p), len(s)] for i, (p, s) in by_idx.items()},
            "chosen": chosen,
            "gap_values": {k: v.tolist() for k, v in g.items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
