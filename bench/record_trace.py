"""Record the small chip trace that ``tests/bench`` checks the reduction on.

    python3 bench/record_trace.py --out bench/testdata/trace

Serves eight short requests through the paged engine at a small size on
the chip, with the harness's host annotations, under the profiler.  Writes
the ``.xplane.pb`` (what ``bench/trace.py`` reads) and ``expected.json``:
the numbers the reduction must give, worked out here from the profiler's
Perfetto JSON, a second export of the same trace that the reduction never
reads.
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def serve_small():
    """A small engine, instrumented as the harness instruments a traced
    run; returns a function that serves eight requests, then sleeps (with
    the engine and its adapter as ``run.engine`` and ``run.adapter``)."""
    import numpy as np

    from bench import family, harness
    from bench.common import seed_key
    from repro.fleet.replica import build_engine
    from repro.models.config import ModelConfig
    from repro.serve.engine import EngineConfig, PagedTransformerModel
    from repro.sharding.rules import Rules

    cfg = ModelConfig(name="small", family="dense", n_layers=2, d_model=256,
                      n_heads=4, n_kv_heads=2, d_ff=512, vocab_size=512,
                      head_dim=64, tp=1)
    params = family.load({"family": cfg.family}).init_weights(cfg)(
        seed_key(1))
    adapter = PagedTransformerModel(params, cfg, Rules.null())
    engine = build_engine(adapter, EngineConfig(
        n_slots=4, max_prompt_len=64, max_new_cap=16, cache_len=80,
        page_size=16))
    span = harness.instrument(engine, adapter, harness.Calls(), traced=True)
    rng = np.random.default_rng(0)
    work = [(rng.integers(0, 512, 32 if i % 2 else 64).astype(np.int32),
             8 if i % 3 else 16) for i in range(8)]

    def run(sleep_s):
        for prompt, n in work:
            with span("submit"):
                engine.submit(prompt, n)
        while engine.has_work:
            engine.step()
            with span("harvest"):
                engine.harvest()
        with span("sleep"):
            time.sleep(sleep_s)

    run(0.0)                      # compiles every shape
    run.engine, run.adapter = engine, adapter
    return run


PROGRAMS = ("jit_paged_group_prefill", "jit_paged_decode1", "jit_run")


def expected_from_perfetto(path) -> dict:
    """The reduction's numbers, worked out from the Perfetto JSON export:
    busy time in the window, time and runs per program, and idle time by
    the innermost ``bench.*`` host span."""
    events = json.loads(gzip.open(path).read())
    if isinstance(events, dict):
        events = events["traceEvents"]
    proc, thread = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            proc[e["pid"]] = e["args"]["name"]
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            thread[(e["pid"], e["tid"])] = e["args"]["name"]
    dev = [p for p, n in proc.items() if n == "/device:TPU:0"][0]
    span = lambda e: (round(e["ts"] * 1000), round((e["ts"] + e["dur"]) * 1000))
    xs = [e for e in events if e.get("ph") == "X"]
    win = span([e for e in xs if e["name"] == "bench.window"][0])
    ops = sorted(span(e) for e in xs if e["pid"] == dev
                 and thread[(dev, e["tid"])] == "XLA Ops")
    mods = [(span(e), e["name"].split("(")[0]) for e in xs if e["pid"] == dev
            and thread[(dev, e["tid"])] == "XLA Modules"]
    host = [(span(e), e["name"]) for e in xs
            if e["name"].startswith("bench.") and e["name"] != "bench.window"]

    busy, cur = [], None            # union of ops, cut to the window
    for a, b in ops:
        a, b = max(a, win[0]), min(b, win[1])
        if a >= b:
            continue
        if cur and a <= cur[1]:
            cur[1] = max(cur[1], b)
        else:
            cur = [a, b]
            busy.append(cur)
    idle, t = [], win[0]
    for a, b in busy:
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    if t < win[1]:
        idle.append((t, win[1]))
    by_host = collections.Counter()
    for lo, hi in idle:
        cuts = sorted({lo, hi} | {x for (a, b), _ in host for x in (a, b)
                                  if lo < x < hi})
        for a, b in zip(cuts, cuts[1:]):
            live = [(s0, n) for (s0, s1), n in host if s0 <= (a + b) / 2 < s1]
            by_host[max(live)[1][6:] if live
                    else "host outside the harness's spans"] += (b - a) * 1e-9
    programs = {}
    for name in PROGRAMS:
        cut = [(max(a, win[0]), min(b, win[1])) for (a, b), n in mods
               if n == name]
        programs[name] = {"seconds": sum(max(0, b - a) for a, b in cut) * 1e-9,
                          "runs": sum(1 for a, b in cut if b > a)}
    return {"window_s": (win[1] - win[0]) * 1e-9,
            "busy_s": sum(b - a for a, b in busy) * 1e-9,
            "programs": programs, "idle_by_host_s": dict(by_host)}


def write_expected(out: pathlib.Path) -> None:
    expected = expected_from_perfetto(out / "perfetto.trace.json.gz")
    (out / "expected.json").write_text(json.dumps(expected, indent=1) + "\n")
    print(json.dumps(expected, indent=1))


def record(out: pathlib.Path):
    """Serve under the profiler and write the ``.xplane.pb`` and the
    Perfetto export under ``out``; returns ``serve_small``'s function."""
    import jax
    run = serve_small()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, create_perfetto_trace=True,
                             profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        run(0.05)
        run(0.05)
    jax.profiler.stop_trace()
    out.mkdir(parents=True, exist_ok=True)
    for pattern, name in (("*.xplane.pb", "trace.xplane.pb"),
                          ("*.trace.json.gz", "perfetto.trace.json.gz")):
        found = glob.glob(f"{tmp}/**/{pattern}", recursive=True)[0]
        shutil.copy(found, out / name)
    shutil.rmtree(tmp)
    return run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(ROOT / "bench/testdata/trace"))
    ap.add_argument("--expected-only", action="store_true",
                    help="rewrite expected.json from the Perfetto file "
                         "already in --out (no chip needed)")
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out)
    if not args.expected_only:
        record(out)
    write_expected(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
