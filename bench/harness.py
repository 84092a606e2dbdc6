"""One run of one cell: set-up, the measured window, the drain, the check.

The window drives the program's ``ServingEngine.step()`` on the paged KV
plane, built through ``repro.fleet.replica.build_engine`` around
``PagedTransformerModel``.  The harness owns the loop: it submits each
request at its due time (never ahead of it), streams each in-flight
request's tokens after every step (``tokens_so_far``) and delivers what
finished (``harvest()``), and sleeps only when the engine has no work.
Arrivals stop when the window closes; what is in flight drains (for at
most ``DRAIN_S``), and its latencies count.

Streaming copies each step's tokens to the host as the step ends.  Without
it the engine copies the tokens only when a request finishes, joining the
blocks since the last copy with ``jnp.concatenate``, which compiles once
per distinct tuple of block lengths: some 70 programs, 7-9 s of compiling,
inside a 50 s window on one v5e.

Set-up is everything from process start to the window: imports, the
weights (one jitted call from the seed), compile-cache reads, and one run
of every program the cell's traffic can reach (each padded prompt length
at each prefill group width, each power-of-two decode stretch).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import pathlib
import shutil
import sys
import tempfile
import time
from types import MappingProxyType
from typing import Dict, List, Mapping, NamedTuple, Optional

import numpy as np

from . import correctness, family, traffic
from . import program_trace as pt
from . import trace as tr
from .common import seed_key

DRAIN_S = 60.0
TRACE_S = 20.0     # a traced run profiles the window's last TRACE_S seconds
METRICS_DIR = pathlib.Path(__file__).resolve().parent / "metrics"
DECODE_CALL = "decode_multi"   # the adapter's decode entry the harness wraps


@dataclasses.dataclass
class Record:
    """One request as its client saw it (times: ``perf_counter`` s)."""
    idx: int
    prompt: np.ndarray
    max_new: int
    due: float
    rid: Optional[int] = None
    first: Optional[float] = None
    delivered: Optional[float] = None
    tokens: Optional[np.ndarray] = None
    rejected: bool = False


class CompileCount:
    """Programs JAX builds (compiled or read from the persistent cache),
    with their names, as its monitoring events report them."""

    def __init__(self):
        import jax
        self.names: List[str] = []
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(str(kw.get("fun_name", "?")))
            self.seconds += duration


class Decode(NamedTuple):
    """One call of the adapter's ``decode_multi``, as the harness saw it:
    ``time`` (``perf_counter`` s, before the call), ``k`` fused steps, each
    live row's depth at the first step, the page pool's occupancy, and in
    a traced run ``program``: the numeric arguments of the program's own
    spans around the call, summed by name (``with_program``)."""
    time: float
    k: int
    depths: List[int]
    occupancy: float
    program: Mapping[str, float] = MappingProxyType({})


@dataclasses.dataclass
class Calls:
    """What the harness saw the engine's layers do: a ``Decode`` record
    per decode call, and a prefill record (time, prompt lengths) per
    prefill call."""
    decode: List[Decode] = dataclasses.field(default_factory=list)
    prefill: List[tuple] = dataclasses.field(default_factory=list)

    def window(self, t0: float, t1: float) -> "Calls":
        inside = lambda c: t0 <= c[0] < t1
        return Calls([c for c in self.decode if inside(c)],
                     [c for c in self.prefill if inside(c)])


def instrument(engine, adapter, calls: Calls, traced: bool):
    """Wrap the calls into each layer on these instances: record what they
    were asked to do and, when traced, mark them on the profiler's host
    timeline.  Returns the span factory for the harness's own spans."""
    if traced:
        import jax
        span = lambda name: jax.profiler.TraceAnnotation(tr.PREFIX + name)
    else:
        span = lambda name: contextlib.nullcontext()
    pool, sched = engine.pool, engine.scheduler

    def wrap(obj, attr, name, note=None):
        fn = getattr(obj, attr)

        def call(*a, **kw):
            if note is not None:
                note(*a, **kw)
            with span(name):
                return fn(*a, **kw)
        setattr(obj, attr, call)

    def note_decode(cache, tok, pos, k):
        depths = [r.prompt_len + r.n_generated
                  for r in sched.active.values() if not r.done]
        calls.decode.append(Decode(time.perf_counter(), int(k), depths,
                                   pool.occupancy))

    def note_prefill(cache, prompts, slots, tok, pos):
        calls.prefill.append((time.perf_counter(),
                              [int(len(p)) for p in prompts]))

    wrap(engine, "step", "engine.step")
    wrap(sched, "plan", "scheduler.plan")
    wrap(pool, "prepare_decode", "pool.prepare_decode")
    wrap(adapter, "prefill", "prefill", note_prefill)
    wrap(adapter, DECODE_CALL, DECODE_CALL, note_decode)
    return span


def with_program(calls: Calls, trace: tr.Trace) -> Calls:
    """``calls`` with each decode record's ``program``: the arguments of
    the program's spans around that call (``program_trace.call_args``).
    The n-th record's call is the n-th of the harness's own spans around
    the adapter's decode in the trace's window; where the two counts
    differ, the records are returned as they are."""
    lo, hi = trace.window
    wraps = [(a, b) for a, b, name in trace.host
             if name == tr.PREFIX + DECODE_CALL and lo <= a < hi]
    if len(wraps) != len(calls.decode):
        print(f"trace: {len(wraps)} decode calls traced, {len(calls.decode)}"
              f" recorded: no program arguments", file=sys.stderr)
        return calls
    args = pt.call_args(trace.spans, wraps)
    return Calls([c._replace(program=a) for c, a in zip(calls.decode, args)],
                 calls.prefill)


def build(cell, seed: int):
    """Weights from the seed, the adapter and the engine."""
    import jax
    from repro.fleet.replica import build_engine
    from repro.serve.engine import PagedTransformerModel
    from repro.sharding.rules import Rules

    fam = family.load(cell.spec)
    cfg = fam.model_config(cell.spec)
    params = fam.init_weights(cfg)(seed_key(seed))
    jax.block_until_ready(params)
    adapter = PagedTransformerModel(params, cfg, Rules.null())
    engine = build_engine(adapter, cell.engine_config())
    return cfg, adapter, engine


def decode_ks(max_output: int) -> List[int]:
    """Every fused decode length the engine can choose: powers of two up
    to the longest remaining budget, ``max_new - 1``."""
    top = max(1, max_output - 1)
    return [1 << i for i in range(top.bit_length())]


def warm_up(cell, adapter, engine) -> None:
    """Run each program the traffic can reach once, on the engine's pool
    with every page map pointing at the trash page: the outputs are thrown
    away and the engine's state is untouched."""
    import jax
    ec = engine.config
    tok, pos = adapter.token_state(ec.n_slots)
    for width in range(1, ec.max_prefill_per_step + 1):
        for length in cell.traffic["prompt_tokens"]["values"]:
            prompts = [np.zeros(length, np.int32)] * width
            _, firsts, tok, pos = adapter.prefill(
                engine.cache, prompts, list(range(width)), tok, pos)
            jax.block_until_ready(firsts)
    for k in decode_ks(cell.max_output):
        out = adapter.decode_multi(engine.cache, tok, pos, k)
        jax.block_until_ready(out)
        np.asarray(out[1])       # the host copy harvest() makes
    del out


class Loop:
    """The harness's side of the window: submit, step, harvest, sleep."""

    def __init__(self, engine, span, cell):
        self.engine, self.span, self.cell = engine, span, cell
        self.by_rid: Dict[int, Record] = {}
        self.records: List[Record] = []
        self.served: List[list] = []      # [start, end] while work is out
        self.outstanding = 0
        self.timers: List[tuple] = []     # (time, fn), run once when due

    def tick(self) -> None:
        now = time.perf_counter()
        while self.timers and self.timers[0][0] <= now:
            self.timers.pop(0)[1]()

    def submit(self, rec: Record) -> None:
        from repro.serve.engine import AdmissionError
        self.records.append(rec)
        with self.span("submit"):
            try:
                rec.rid = self.engine.submit(rec.prompt, rec.max_new)
            except AdmissionError:
                rec.rejected = True
                return
        self.by_rid[rec.rid] = rec
        if self.outstanding == 0:
            self.served.append([time.perf_counter(), None])
        self.outstanding += 1

    def step(self) -> List[Record]:
        """One engine step, each in-flight request's tokens streamed to its
        client (the engine's ``tokens_so_far``: one host copy of the step's
        new tokens), then the finished requests delivered (``harvest``)."""
        self.engine.step()
        with self.span("stream"):
            for rid in list(self.engine.scheduler.active):
                self.engine.tokens_so_far(rid)
        with self.span("harvest"):
            done = self.engine.harvest()
        now = time.perf_counter()
        out = []
        for rid, toks in done.items():
            rec = self.by_rid[rid]
            rec.delivered, rec.tokens = now, np.asarray(toks)
            rec.first = self.engine.completed[rid].first_token_wall
            out.append(rec)
        self.outstanding -= len(out)
        if out and self.outstanding == 0:
            self.served[-1][1] = now
        return out

    def sleep(self, until: float) -> None:
        if self.timers:
            until = min(until, self.timers[0][0])
        with self.span("sleep"):
            time.sleep(max(0.0, until - time.perf_counter()))

    def drain(self, deadline: float) -> None:
        while self.outstanding and time.perf_counter() < deadline:
            if self.engine.has_work:
                self.step()
            else:
                raise RuntimeError("requests outstanding but the engine "
                                   "has no work")


def drive(loop: Loop, cell, seed: int, seconds: float, vocab: int,
          t0: float) -> None:
    """Offer the cell's traffic from ``t0`` until ``t0 + seconds``."""
    t_end = t0 + seconds
    mix = cell.traffic
    if mix["loop"] == "open":
        pending = traffic.open_schedule(mix, seconds, seed, vocab)
        i = 0
        while i < len(pending):
            loop.tick()
            now = time.perf_counter()
            while i < len(pending) and t0 + pending[i].due <= now:
                q = pending[i]
                loop.submit(Record(q.idx, q.prompt, q.max_new, t0 + q.due))
                i += 1
            if loop.engine.has_work:
                loop.step()
            elif i < len(pending):
                loop.sleep(t0 + pending[i].due)
        while time.perf_counter() < t_end:
            loop.tick()
            if loop.engine.has_work:
                loop.step()
            else:
                loop.sleep(t_end)
    elif mix["loop"] == "closed":
        stream = traffic.ClosedStream(mix, seed, vocab)
        waiting: List[float] = []          # clients' next send times
        for _ in range(stream.clients):
            q = stream.take()
            loop.submit(Record(q.idx, q.prompt, q.max_new, t0))
        while time.perf_counter() < t_end:
            loop.tick()
            now = time.perf_counter()
            while waiting and waiting[0] <= now:
                waiting.pop(0)
                q = stream.take()
                loop.submit(Record(q.idx, q.prompt, q.max_new, now))
            if loop.engine.has_work:
                for _ in loop.step():
                    waiting.append(time.perf_counter() + stream.think_s)
            else:
                loop.sleep(min(waiting + [t_end]))
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")


def end_to_end(name: str, delivered: List[Record], tokens: int,
               window_s: float, setup_s: float) -> Optional[float]:
    """An end-to-end metric by its name: ``ttft_p<q>_ms`` (due time to the
    first token) or ``tpot_p<q>_ms`` (first token to delivery, per later
    token) at percentile ``q`` over the delivered requests,
    ``output_tok_s`` or ``setup_s``."""
    kind, _, rest = name.partition("_p")
    if kind in ("ttft", "tpot") and rest.endswith("_ms"):
        if kind == "ttft":
            v = [(r.first - r.due) * 1e3 for r in delivered]
        else:
            v = [(r.delivered - r.first) * 1e3 / (r.max_new - 1)
                 for r in delivered if r.max_new > 1]
        return float(np.percentile(v, float(rest[:-3]))) if v else None
    return {"output_tok_s": tokens / window_s, "setup_s": setup_s}[name]


class Context:
    """What a per-layer metric's reader may read: besides the cell, the
    configuration, the peaks, the window's calls and the trace, the
    program's spans that overlap the window (``spans``) and the decode
    programs' scope map (``scope_map``, ``program_trace.scope_map``)."""

    def __init__(self, cell, cfg, peaks, calls: Calls, trace, clip_ns,
                 served_ns, served_s, scope_map=None):
        self.cell, self.cfg, self.peaks = cell, cfg, peaks
        self.n_slots = cell.n_slots
        self.calls, self.trace = calls, trace
        self.window_ns, self.served_ns = clip_ns, served_ns
        self.served_s = served_s
        lo, hi = min(a for a, _ in clip_ns), max(b for _, b in clip_ns)
        self.spans = [s for s in trace.spans if s[1] > lo and s[0] < hi]
        self.scope_map = scope_map


def read_metric(name: str, ctx: Context) -> Optional[float]:
    """Run ``bench/metrics/<name>.py``'s ``read(ctx)``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", METRICS_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache in the directory the program
    chooses (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``),
    keeping every program however small or quick to build, so that only
    the first run of a cell in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import use_compile_cache as program_dir
    path = program_dir()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def free(*trees) -> None:
    import jax
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
    gc.collect()


def traced_context(cell, cfg, kind: str, trace_dir: str, seg_t0: float,
                   served_perf, calls: Calls, texts: dict) -> Context:
    """What the readers read of a traced segment that opened at ``seg_t0``
    (``perf_counter`` s): its trace, the decode records with the program's
    span arguments, and the scope map of the decode programs that ran,
    bound from ``texts`` (``program_trace.decode_texts``)."""
    from . import peaks
    t_load = time.perf_counter()
    trace = tr.load(trace_dir)
    print(f"trace: {sum(map(len, trace.ops))} device ops read in "
          f"{time.perf_counter() - t_load:.1f}s", file=sys.stderr)
    lo, hi = trace.window
    to_ns = lambda t: lo + (t - seg_t0) * 1e9
    served = tr.Clip((max(to_ns(a), lo), min(to_ns(b), hi))
                     for a, b in served_perf
                     if to_ns(b) > lo and to_ns(a) < hi).iv
    calls = with_program(calls, trace)
    smap = pt.scope_map(trace, calls.decode, texts,
                        family.load(cell.spec).SCOPES)
    return Context(cell, cfg, peaks.peaks(kind), calls, trace,
                   [trace.window], served,
                   sum(b - a for a, b in served) * 1e-9, scope_map=smap)


def device_scopes(ctx: Context) -> Dict[str, float]:
    """The decode programs' device seconds in the window by scope, with
    ``program_trace.UNMAPPED`` always present."""
    by_scope = pt.scope_ns(ctx.trace, pt.DECODE_PROGRAMS, ctx.window_ns,
                           ctx.scope_map or {})
    return {pt.UNMAPPED: 0.0, **{k: v * 1e-9 for k, v in by_scope.items()}}


def per_layer(ctx: Context) -> dict:
    """The per-layer metrics, device busy time and breakdown of a traced
    segment."""
    metrics = {}
    for m in ctx.cell.per_layer:
        v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    clip, trace = ctx.window_ns, ctx.trace
    lo, hi = trace.window
    scopes = device_scopes(ctx)
    top = sorted(scopes.items(), key=lambda kv: -kv[1])
    top = [kv for kv in top if kv[0] != pt.UNMAPPED][:9]
    return {"metrics": metrics,
            "device": {"busy_s": tr.busy_ns(trace, clip) * 1e-9,
                       "window_s": (hi - lo) * 1e-9},
            "breakdown": {
                "device_ops": [list(x) for x in tr.top_ops(trace, clip)],
                "idle_gaps": [list(x) for x in tr.gaps_by_host(
                    trace, ctx.served_ns)],
                "device_scopes": [list(x) for x in top]
                + [[pt.UNMAPPED, scopes[pt.UNMAPPED]]]},
            "scope_coverage": pt.coverage(scopes),
            "program_s": tr.program_seconds(trace, clip)}


def check(cell, cfg, seed: int, delivered: List[Record], undelivered: int,
          limits: dict):
    """Each number compared beside its limit, whether all hold, and how
    many served tokens the reference checked.  Runs after the program's
    state is freed."""
    positions, rows = correctness.budget(cell)
    by_idx = {r.idx: (r.prompt, r.tokens) for r in delivered
              if r.tokens.shape == (r.max_new,)}
    chosen = correctness.sample(by_idx, seed, positions, rows)
    gap = correctness.gaps(cell.spec, cfg, seed,
                           [by_idx[i][0] for i in chosen],
                           [by_idx[i][1] for i in chosen], positions,
                           rows)["served"] if chosen else np.zeros(0)
    values = dict(correctness.numbers(gap),
                  bad_outputs=correctness.bad_outputs(delivered,
                                                      cfg.vocab_size),
                  undelivered=undelivered)
    checks, ok = correctness.judge(
        values, dict(limits, bad_outputs=0, undelivered=0))
    return checks, ok, sum(len(by_idx[i][1]) for i in chosen)


def run_cell(cell, seed: int, seconds: float, traced: bool,
             t_start: float, limits: Optional[dict] = None) -> dict:
    """Set up, measure, drain and check one run.  Returns the result
    line's fields, ``checks`` last (each number compared beside its
    limit)."""
    import jax

    compiles = CompileCount()
    cfg, adapter, engine = build(cell, seed)
    warm_up(cell, adapter, engine)
    calls = Calls()
    span = instrument(engine, adapter, calls, traced)
    loop = Loop(engine, span, cell)
    dev = jax.devices()[0]
    tmp, seg = tempfile.mkdtemp(prefix="bench-trace-") if traced else None, {}

    def start_segment():
        # in step with the device, so the traced programs are the ones the
        # segment's calls dispatched
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.block_until_ready(engine.cache)
        jax.profiler.start_trace(tmp, profiler_options=opts)
        seg["span"] = span("window")
        seg["span"].__enter__()
        seg["t0"] = time.perf_counter()

    n_built, built_s = len(compiles.names), compiles.seconds
    stats0 = engine.progress()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    if traced:
        loop.timers.append((t0 + max(0.0, seconds - TRACE_S), start_segment))
        loop.tick()
    drive(loop, cell, seed, seconds, cfg.vocab_size, t0)
    jax.block_until_ready(engine.cache)
    t1 = time.perf_counter()
    stats1 = engine.progress()
    in_window = compiles.names[n_built:]
    in_window_s = compiles.seconds - built_s
    if traced:
        seg["span"].__exit__(None, None, None)
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"trace: last {t1 - seg['t0']:.1f}s of the window, written in "
              f"{time.perf_counter() - t_stop:.1f}s", file=sys.stderr)
    loop.drain(time.perf_counter() + DRAIN_S)
    t_drained = time.perf_counter()
    for span_ in loop.served:
        span_[1] = span_[1] or t_drained
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if traced:
        # the decode programs' texts, after the window and the drain: the
        # twins compiled here build nothing the window or set-up ran
        t_map = time.perf_counter()
        seg_calls = calls.window(seg["t0"], t1)
        texts = pt.decode_texts(adapter, engine,
                                {c.k for c in seg_calls.decode})
        map_s = time.perf_counter() - t_map
        print(f"trace: {len(texts)} decode programs' texts in {map_s:.1f}s",
              file=sys.stderr)

    recs = loop.records
    delivered = [r for r in recs if r.delivered is not None]
    undelivered = sum(1 for r in recs if r.rid is not None
                      and r.delivered is None)
    result = {"correct": None, "attempted": len(recs),
              "failed": sum(r.rejected for r in recs) + undelivered,
              "metrics": {}}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    if traced:
        layers = per_layer(traced_context(cell, cfg, dev.device_kind, tmp,
                                          seg["t0"], loop.served, seg_calls,
                                          texts))
        layers["scope_map_s"] = map_s
        shutil.rmtree(tmp, ignore_errors=True)
        result["metrics"] = layers.pop("metrics")
        device.update(layers.pop("device"))
        result["device"] = device
        result.update(layers)
    else:
        tokens = ((stats1["decode_tokens"] - stats0["decode_tokens"])
                  + (stats1["prefill_count"] - stats0["prefill_count"]))
        for m in cell.end_to_end:
            v = end_to_end(m["name"], delivered, tokens, t1 - t0, setup_s)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"] = device
    result["compiles_in_window"] = len(in_window)
    result["compiled_in_window"] = sorted(set(in_window))
    result["compile_s_in_window"] = in_window_s

    # the check: the program's state freed first, then the plain reference
    free(adapter.params, engine.cache)
    del adapter, engine, loop.engine
    checks, ok, checked = check(cell, cfg, seed, delivered, undelivered,
                                limits or cell.spec["check"])
    result["checked_tokens"] = checked
    result["correct"] = ok
    result["checks"] = checks
    return result
