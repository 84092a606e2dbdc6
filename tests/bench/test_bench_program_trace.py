"""The program's own spans and scopes, and the readers built on them.

On the CPU: a small paged engine run under ``jax.profiler`` writes every
``serve.*`` span the engine has, nested as the engine nests them, with the
arguments the harness sees; the compiled decode and prefill programs carry
every named scope; the readers give known values on a synthetic trace.
On a trace recorded on a TPU v5e (``bench/testdata/trace_program``, from
``bench/record_program_trace.py``): the reduction gives the numbers worked
out from the profiler's Perfetto export of the same trace.
"""

import collections
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, family, harness
from bench import program_trace as pt
from bench import trace as tr
from bench.common import seed_key
from smallcell import small_spec, small_traffic

DATA = pathlib.Path(__file__).resolve().parents[2] / "bench/testdata"
REL = 1e-3

STEP_CALLS = ("serve.plan", "serve.prefill", "serve.prefill_wait",
              "serve.prepare_decode", "serve.page_tables", "serve.decode")
HOST_CALLS = ("serve.fetch_tokens", "serve.join_tokens", "serve.fetch_firsts",
              "serve.harvest")


@pytest.fixture(scope="module")
def small_run(tmp_path_factory):
    """Twelve requests through the small cell's paged engine, streamed and
    harvested as the harness does, under the profiler."""
    cell = cells.Cell("small.open", 1, small_spec(), small_traffic("open"),
                      [], [])
    cfg, adapter, engine = harness.build(cell, 5)
    harness.warm_up(cell, adapter, engine)
    calls = harness.Calls()
    harness.instrument(engine, adapter, calls, traced=True)
    rng = np.random.default_rng(0)
    out = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(out)
    for i in range(12):
        engine.submit(rng.integers(0, 512, (16, 48)[i % 2]).astype(np.int32),
                      (4, 12)[i % 3 == 0])
        if i % 4 == 3:
            engine.step()
    while engine.has_work:
        engine.step()
        for rid in list(engine.scheduler.active):
            engine.tokens_so_far(rid)
        engine.harvest()
    jax.profiler.stop_trace()
    return pt.load_spans(out), calls, cell, cfg


def test_engine_writes_every_span(small_run):
    spans, _, _, _ = small_run
    names = {name for _, _, name, _ in spans}
    assert {"serve.step", "serve.queue_wait", "serve.request",
            "serve.queue_depth", "serve.pool_occupancy", *STEP_CALLS,
            *HOST_CALLS} <= names
    by = collections.defaultdict(list)
    for s in spans:
        by[s[2]].append(s)
    steps = by["serve.step"]
    assert [s[3]["step"] for s in steps] == list(range(1, len(steps) + 1))
    inside = lambda s, outer: any(o[0] <= s[0] and s[1] <= o[1]
                                  for o in outer)
    for name in STEP_CALLS:
        assert all(inside(s, steps) for s in by[name]), name
    for name in HOST_CALLS:       # the harness's loop calls these between
        assert not any(inside(s, steps) for s in by[name]), name
    assert all(inside(s, by["serve.harvest"]) or not inside(s, steps)
               for s in by["serve.fetch_tokens"])
    rids = {s[3]["rid"] for s in by["serve.request"]}
    assert rids == {s[3]["rid"] for s in by["serve.queue_wait"]}
    assert len(rids) == 12
    assert all({"prompt_len", "max_new", "tokens"} <= set(s[3])
               for s in by["serve.request"])
    assert all("value" in s[3] for s in by["serve.pool_occupancy"])


def test_span_arguments_match_the_harness(small_run):
    spans, calls, _, _ = small_run
    decode = [s[3] for s in spans if s[2] == "serve.decode"]
    assert [(d["k"], d["rows"], d["depth_sum"]) for d in decode] == [
        (k, len(depths), sum(depths)) for _, k, depths, _ in calls.decode]
    prefill = [s[3] for s in spans if s[2] == "serve.prefill"]
    assert [(p["n"], p["tokens"], p["padded_len"]) for p in prefill] == [
        (len(n), sum(n), max(n)) for _, n in calls.prefill]
    steps = [s[3] for s in spans if s[2] == "serve.step"]
    assert sum(s["k"] for s in steps) == sum(k for _, k, _, _ in
                                             calls.decode)
    assert sum(s["n_admit"] for s in steps) == 12
    fetched = [s[3]["rows"] for s in spans if s[2] == "serve.fetch_tokens"]
    assert sum(fetched) == sum(k for _, k, _, _ in calls.decode)


def test_compiled_programs_carry_every_scope(small_run):
    _, calls, cell, cfg = small_run
    ks = {k for _, k, _, _ in calls.decode}
    assert 1 in ks and len(ks) > 1
    texts = pt.decode_hlo_texts(cell, cfg, ks)
    assert set(texts) == {"jit_paged_decode1", "jit_run"}
    decode = set(pt.scope_map(texts).values())
    assert {"kv_write", "attention", "mlp", "lm_head", "embed",
            pt.UNSCOPED} <= decode

    from repro.models import transformer as T
    from repro.serve.engine import PagedTransformerModel
    from repro.sharding.rules import Rules
    ec = cell.engine_config()
    params = jax.eval_shape(lambda: family.load(cell.spec).init_weights(cfg)(
        seed_key(0)))
    adapter = PagedTransformerModel(params, cfg, Rules.null())
    pool = jax.eval_shape(
        lambda: T.init_cache(cfg, ec.pool_pages + 1, ec.page_size))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = adapter._paged_prefill.lower(
        ec.pages_per_slot * ec.page_size, params, i32(2, 48), i32(2),
        i32(2), i32(2, ec.pages_per_slot), pool, i32(ec.n_slots),
        i32(ec.n_slots)).compile().as_text()
    prefill = set(pt.scope_map({"jit_paged_group_prefill": [text]}).values())
    assert {"embed", "attention", "kv_write", "mlp", "lm_head",
            "page_scatter"} <= prefill


def test_scope_of_and_clashing_variants():
    assert pt.scope_of("jit(run)/while/body/closed_call/kv_write/dus") == \
        "kv_write"
    assert pt.scope_of("jit(run)/page_gather/attention/x") == "page_gather"
    assert pt.scope_of("jit(run)/while/body/add") == pt.UNSCOPED
    one = ('  %fusion.1 = f32[2] fusion(%p), metadata={op_name="a/mlp/dot"}\n'
           '  ROOT %copy.2 = f32[2] copy(%fusion.1)\n')
    two = one.replace("a/mlp/dot", "a/lm_head/dot")
    assert pt.scope_map({"m": [one]}) == {("m", "fusion.1"): "mlp",
                                          ("m", "copy.2"): pt.UNSCOPED}
    assert pt.scope_map({"m": [one, two]}) == {("m", "copy.2"): pt.UNSCOPED}


def synthetic():
    """Two decode calls (k = 4, then 1) and a prefill on one device, in a
    1000 ns window; the harness's stream span and the program's spans on
    the host."""
    modules = [[(0, 400, "jit_run"), (450, 600, "jit_paged_group_prefill"),
                (700, 900, "jit_paged_decode1")]]
    ops = [sorted([(0, 100, "g.1"), (100, 300, "while.1"), (110, 200, "a.1"),
                   (200, 300, "kv.1"), (300, 350, "head.1"),
                   (350, 400, "s.1"), (450, 600, "p.1"), (700, 760, "g.1"),
                   (760, 860, "head.1"), (860, 900, "x.1")])]
    trace = tr.Trace(window=(0, 1000), modules=modules, ops=ops,
                     host=[(400, 700, "bench.stream")])
    smap = {("jit_run", "g.1"): "page_gather", ("jit_run", "a.1"):
            "attention", ("jit_run", "kv.1"): "kv_write",
            ("jit_run", "head.1"): "lm_head", ("jit_run", "s.1"):
            "page_scatter", ("jit_paged_decode1", "g.1"): "page_gather",
            ("jit_paged_decode1", "head.1"): "lm_head",
            ("jit_paged_decode1", "x.1"): pt.UNSCOPED}
    spans = [(0, 420, "serve.step", {"step": 1}),
             (10, 30, "serve.decode", {"k": 4}),
             (405, 440, "serve.fetch_tokens", {"blocks": 1}),
             (0, 990, "serve.request", {"rid": 0}),
             (610, 950, "serve.step", {"step": 2}),
             (620, 650, "serve.page_tables", {})]
    calls = harness.Calls(decode=[(0.0, 4, [5, 9], 0.5),
                                  (1.0, 1, [6], 0.5)])
    ctx = types.SimpleNamespace(trace=trace, calls=calls, scope_map=smap,
                                window_ns=[trace.window])
    return trace, spans, ctx


def test_readers_on_a_synthetic_trace():
    trace, spans, ctx = synthetic()
    # decode steps: 4 + 1.  KV: gather 100 + 60, kv 100, scatter 50
    assert harness.read_metric("kv_cache_ms_per_step", ctx) == \
        pytest.approx(310e-6 / 5)
    assert harness.read_metric("lm_head_ms_per_step", ctx) == \
        pytest.approx(150e-6 / 5)
    by_scope = pt.scope_ns(trace, pt.DECODE_PROGRAMS, [trace.window],
                           ctx.scope_map)
    assert by_scope == {"page_gather": 160, "attention": 90,
                        "kv_write": 100, "lm_head": 150,
                        "page_scatter": 50, pt.UNSCOPED: 40}
    assert pt.coverage(by_scope) == 1.0
    # idle: 400-450, 600-700, 900-1000 over two serve.step spans
    assert pt.host_idle_ms_per_step(trace, spans, [trace.window]) == \
        pytest.approx(250e-6 / 2)
    # 400-405 step, 405-440 fetch, 440-450 stream; 600-610 stream,
    # 610-620 step, 620-650 page_tables, 650-700 step; 900-950 step, then
    # only the request's residency (which names no gap)
    gaps = dict(pt.gaps_by_program(trace, spans, [trace.window]))
    assert gaps == pytest.approx({
        "serve.step": 115e-9, "serve.fetch_tokens": 35e-9,
        "bench.stream": 20e-9, "serve.page_tables": 30e-9,
        tr.UNTRACED: 50e-9})


def test_readers_read_nothing_rather_than_a_wrong_number():
    _, _, ctx = synthetic()
    unmapped = dict(ctx.scope_map)
    del unmapped[("jit_run", "a.1")]          # 90 of 590 ns unmapped
    for smap in (unmapped, {k: pt.UNSCOPED for k in ctx.scope_map}):
        c = types.SimpleNamespace(**dict(vars(ctx), scope_map=smap))
        assert harness.read_metric("kv_cache_ms_per_step", c) is None
        assert harness.read_metric("lm_head_ms_per_step", c) is None
    c = types.SimpleNamespace(**dict(vars(ctx), calls=harness.Calls()))
    assert harness.read_metric("kv_cache_ms_per_step", c) is None
    trace, _, _ = synthetic()
    assert pt.host_idle_ms_per_step(trace, [], [trace.window]) is None


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "trace_program"
    texts = json.loads(__import__("gzip").decompress(
        (path / "hlo.json.gz").read_bytes()))
    return (tr.load(str(path / "trace.xplane.pb")),
            pt.load_spans(str(path / "trace.xplane.pb")), texts,
            json.loads((path / "expected.json").read_text()))


def test_recorded_chip_trace_reduces_to_the_perfetto_numbers(recorded):
    trace, spans, texts, want = recorded
    win = [trace.window]
    steps = sum(s["k"] for a, _, n, s in spans
                if n == "serve.decode" and win[0][0] <= a < win[0][1])
    assert steps == want["decode_steps"]
    by_scope = pt.scope_ns(trace, pt.DECODE_PROGRAMS, win,
                           pt.scope_map(texts))
    assert pt.coverage(by_scope) == pytest.approx(want["scope_coverage"],
                                                  rel=REL)
    assert pt.coverage(by_scope) >= pt.MIN_COVERAGE
    # the .xplane.pb reader keeps whole nanoseconds, the export picoseconds:
    # up to 1 ns less per operation, hundreds of ~50-1000 ns operations
    ops = want["device_scope_ops"]
    for scope, s in want["device_scopes_s"].items():
        assert by_scope.get(scope, 0) * 1e-9 == pytest.approx(
            s, abs=ops[scope] * 1e-9)
    for name, scopes in (("kv_cache_ms_per_step", pt.KV_SCOPES),
                         ("lm_head_ms_per_step", ("lm_head",))):
        assert pt.scoped_ms(by_scope, scopes, steps) == pytest.approx(
            want[name], abs=sum(ops[s] for s in scopes) * 1e-6 / steps)
    assert pt.host_idle_ms_per_step(trace, spans, win) == pytest.approx(
        want["host_idle_ms_per_step"], rel=REL)
    gaps = dict(pt.gaps_by_program(trace, spans, win, n=100))
    assert set(gaps) == set(want["idle_by_program_s"])
    for name, s in want["idle_by_program_s"].items():
        assert gaps[name] == pytest.approx(s, rel=REL, abs=1e-9)
