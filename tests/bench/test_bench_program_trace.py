"""The program's own spans and scopes, and the readers built on them.

On the CPU: a small paged engine run under ``jax.profiler`` writes every
``serve.*`` span the engine has, nested as the engine nests them, with the
arguments the harness sees, and each decode record carries them; the
executables the engine dispatched, with their named twins, carry every
named scope; the readers give known values on a synthetic trace, with two
variants of one program told apart by their ids and a scope that only a
new family names.  On a trace recorded on a TPU v5e
(``bench/testdata/trace_program``, from ``bench/record_program_trace.py``):
the reduction gives the numbers worked out from the profiler's Perfetto
export of the same trace.
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
from bench.record_program_trace import by_length, load_texts
from smallcell import small_spec, small_traffic

SCOPES = family.load(small_spec()).SCOPES
KV = ("page_gather", "page_scatter", "kv_write")

DATA = pathlib.Path(__file__).resolve().parents[2] / "bench/testdata"
REL = 1e-3

STEP_CALLS = ("serve.plan", "serve.prefill", "serve.prefill_wait",
              "serve.prepare_decode", "serve.page_tables", "serve.decode")
HOST_CALLS = ("serve.fetch_tokens", "serve.join_tokens", "serve.fetch_firsts",
              "serve.harvest")


@pytest.fixture(scope="module")
def small_run(tmp_path_factory):
    """Twelve requests through the small cell's paged engine, streamed and
    harvested as the harness does, under the profiler: the program's spans,
    the harness's records, the cell, its configuration, the trace, the
    adapter and the engine."""
    cell = cells.Cell("small.open", 1, small_spec(), small_traffic("open"),
                      [], [])
    cfg, adapter, engine = harness.build(cell, 5)
    harness.warm_up(cell, adapter, engine)
    calls = harness.Calls()
    harness.instrument(engine, adapter, calls, traced=True)
    rng = np.random.default_rng(0)
    out = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        for i in range(12):
            engine.submit(rng.integers(0, 512, (16, 48)[i % 2]).astype(
                np.int32), (4, 12)[i % 3 == 0])
            if i % 4 == 3:
                engine.step()
        while engine.has_work:
            engine.step()
            for rid in list(engine.scheduler.active):
                engine.tokens_so_far(rid)
            engine.harvest()
    jax.profiler.stop_trace()
    trace = tr.load(out)
    return trace.spans, calls, cell, cfg, trace, adapter, engine


def test_engine_writes_every_span(small_run):
    spans = small_run[0]
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
    spans, calls = small_run[:2]
    decode = [s[3] for s in spans if s[2] == "serve.decode"]
    assert [(d["k"], d["rows"], d["depth_sum"]) for d in decode] == [
        (c.k, len(c.depths), sum(c.depths)) for c in calls.decode]
    prefill = [s[3] for s in spans if s[2] == "serve.prefill"]
    assert [(p["n"], p["tokens"], p["padded_len"]) for p in prefill] == [
        (len(n), sum(n), max(n)) for _, n in calls.prefill]
    steps = [s[3] for s in spans if s[2] == "serve.step"]
    assert sum(s["k"] for s in steps) == sum(c.k for c in calls.decode)
    assert sum(s["n_admit"] for s in steps) == 12
    fetched = [s[3]["rows"] for s in spans if s[2] == "serve.fetch_tokens"]
    assert sum(fetched) == sum(c.k for c in calls.decode)


def test_decode_records_carry_the_program_span_arguments(small_run):
    _, calls, cell, _, trace, _, engine = small_run
    assert all(c.program == {} for c in calls.decode)   # as recorded
    got = harness.with_program(calls, trace).decode
    assert len(got) == len(calls.decode) > 1
    n_pages = engine.pool.n_pages
    for c in got:
        # serve.decode's numbers, and nothing of the step around it
        assert c.program["k"] == c.k
        assert c.program["rows"] == len(c.depths)
        assert c.program["depth_sum"] == sum(c.depths)
        assert c.program["pages_used"] == round(c.occupancy * n_pages)
        assert "n_admit" not in c.program and "step" not in c.program
    assert [c[:4] for c in got] == [c[:4] for c in calls.decode]


def test_compiled_programs_carry_every_scope(small_run):
    calls, cell, cfg, _, adapter, engine = small_run[1:]
    ks = {c.k for c in calls.decode}
    assert 1 in ks and len(ks) > 1
    texts = pt.decode_texts(adapter, engine, ks)
    assert set(texts) == ks
    decode = set()
    for ran, named in texts.values():
        ops = pt.executable_scopes(ran, named, SCOPES)
        assert ops is not None and set(ops) == {
            x[0] for x in pt.instructions(ran, SCOPES)}
        decode |= set(ops.values())
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
    with pt.full_name_paths():
        text = adapter._paged_prefill.lower(
            ec.pages_per_slot * ec.page_size, params, i32(2, 48), i32(2),
            i32(2), i32(2, ec.pages_per_slot), pool, i32(ec.n_slots),
            i32(ec.n_slots)).compile().as_text()
    prefill = {x[2] for x in pt.instructions(text, SCOPES)}
    assert {"embed", "attention", "kv_write", "mlp", "lm_head",
            "page_scatter"} <= prefill


def test_scope_of_and_clashing_variants():
    assert pt.scope_of("jit(run)/while/body/closed_call/kv_write/dus",
                       SCOPES) == "kv_write"
    assert pt.scope_of("jit(run)/page_gather/attention/x", SCOPES) == \
        "page_gather"
    assert pt.scope_of("jit(run)/while/body/add", SCOPES) == pt.UNSCOPED
    assert pt.scope_of("jit(run)/experts/dot", SCOPES) == pt.UNSCOPED
    assert pt.scope_of("jit(run)/experts/dot", ("experts",)) == "experts"
    one = ('  %fusion.1 = f32[2] fusion(%p), metadata={op_name="a/mlp/dot"}\n'
           '  ROOT %copy.2 = f32[2] copy(%fusion.1)\n')
    two = one.replace("a/mlp/dot", "a/lm_head/dot")
    # two variants of one program share an operation's name, each with
    # its own scope: each executable keeps its own
    assert pt.executable_scopes(one, one, SCOPES) == {
        "fusion.1": "mlp", "copy.2": pt.UNSCOPED}
    assert pt.executable_scopes(two, two, SCOPES) == {
        "fusion.1": "lm_head", "copy.2": pt.UNSCOPED}
    # the executable that ran, without name paths, takes its scopes from
    # a twin whose instructions are named otherwise, place by place
    ran = one.replace("a/mlp/dot", "dot")
    twin = two.replace("fusion.1", "dot.5").replace("copy.2", "copy.9")
    assert pt.executable_scopes(ran, twin, SCOPES) == {
        "fusion.1": "lm_head", "copy.2": pt.UNSCOPED}
    # ... and none from a twin that is another program
    for other in (twin.replace("f32[2] copy", "f32[2] negate"),
                  twin.replace("f32[2] copy(", "f16[2] copy("),
                  twin + "  %add.3 = f32[2] add(%a, %b)\n"):
        assert pt.executable_scopes(ran, other, SCOPES) is None


def synthetic():
    """Two decode calls (k = 4, then 1) and a prefill on one device, in a
    1000 ns window; the harness's stream span and the program's spans on
    the host."""
    modules = [[(0, 400, "jit_run", "7"),
                (450, 600, "jit_paged_group_prefill", "3"),
                (700, 900, "jit_paged_decode1", "5")]]
    ops = [sorted([(0, 100, "g.1"), (100, 300, "while.1"), (110, 200, "a.1"),
                   (200, 300, "kv.1"), (300, 350, "head.1"),
                   (350, 400, "s.1"), (450, 600, "p.1"), (700, 760, "g.1"),
                   (760, 860, "head.1"), (860, 900, "x.1")])]
    trace = tr.Trace(window=(0, 1000), modules=modules, ops=ops,
                     host=[(400, 700, "bench.stream")])
    smap = {("jit_run", "7"): {"g.1": "page_gather", "a.1": "attention",
                               "kv.1": "kv_write", "head.1": "lm_head",
                               "s.1": "page_scatter"},
            ("jit_paged_decode1", "5"): {"g.1": "page_gather",
                                         "head.1": "lm_head",
                                         "x.1": pt.UNSCOPED}}
    spans = [(0, 420, "serve.step", {"step": 1}),
             (10, 30, "serve.decode", {"k": 4}),
             (405, 440, "serve.fetch_tokens", {"blocks": 1}),
             (0, 990, "serve.request", {"rid": 0}),
             (610, 950, "serve.step", {"step": 2}),
             (620, 650, "serve.page_tables", {})]
    calls = harness.Calls(decode=[harness.Decode(0.0, 4, [5, 9], 0.5),
                                  harness.Decode(1.0, 1, [6], 0.5)])
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
    unmapped = {run: dict(ops) for run, ops in ctx.scope_map.items()}
    del unmapped[("jit_run", "7")]["a.1"]     # 90 of 590 ns unmapped
    unscoped = {run: {op: pt.UNSCOPED for op in ops}
                for run, ops in ctx.scope_map.items()}
    for smap in (unmapped, unscoped, {}, None):
        c = types.SimpleNamespace(**dict(vars(ctx), scope_map=smap))
        assert harness.read_metric("kv_cache_ms_per_step", c) is None
        assert harness.read_metric("lm_head_ms_per_step", c) is None
    c = types.SimpleNamespace(**dict(vars(ctx), calls=harness.Calls()))
    assert harness.read_metric("kv_cache_ms_per_step", c) is None
    trace, _, _ = synthetic()
    assert pt.host_idle_ms_per_step(trace, [], [trace.window]) is None


def test_variants_of_one_program_are_read_through_their_ids():
    """Two fused lengths compile to two executables of one program,
    ``jit_run``, that share an operation's name under different scopes:
    each run is read by the executable its id is bound to."""
    text = lambda scope: (
        f'  %f.1 = f32[2] fusion(%p), metadata={{op_name="jit(run)/while/'
        f'body/{scope}/dot"}}\n  ROOT %c.2 = f32[2] copy(%f.1)\n')
    texts = {2: (text("mlp"), text("mlp")),
             4: (text("lm_head"), text("lm_head"))}
    trace = tr.Trace(
        window=(0, 1000), host=[],
        modules=[[(0, 100, "jit_run", "11"), (200, 300, "jit_run", "22"),
                  (400, 500, "jit_run", "11")]],
        ops=[[(0, 80, "f.1"), (80, 100, "c.2"), (200, 290, "f.1"),
              (290, 300, "c.2"), (400, 480, "f.1"), (480, 500, "c.2")]])
    call = lambda k: harness.Decode(0.0, k, [5], 0.5)
    smap = pt.scope_map(trace, [call(2), call(4), call(2)], texts, SCOPES)
    assert smap == {("jit_run", "11"): {"f.1": "mlp", "c.2": pt.UNSCOPED},
                    ("jit_run", "22"): {"f.1": "lm_head",
                                        "c.2": pt.UNSCOPED}}
    assert pt.scope_ns(trace, ["jit_run"], [trace.window], smap) == {
        "mlp": 160, "lm_head": 90, pt.UNSCOPED: 50}
    # an id bound to two lengths is left out; so is every run where the
    # runs and the calls do not pair up
    assert set(pt.scope_map(trace, [call(2), call(4), call(4)], texts,
                            SCOPES)) == {("jit_run", "22")}
    assert pt.scope_map(trace, [call(2), call(4)], texts, SCOPES) == {}


def test_a_new_family_names_its_own_scopes(tmp_path, monkeypatch):
    """A family that declares an ``experts`` scope, and a reader of it,
    as new files only: the reader reads the operations under it."""
    assert not hasattr(pt, "SCOPES")     # no scope list of its own
    families, metrics = tmp_path / "families", tmp_path / "metrics"
    families.mkdir()
    metrics.mkdir()
    (families / "moe_toy.py").write_text(
        (family.DIR / "dense.py").read_text()
        + '\nSCOPES = SCOPES + ("router", "experts")\n')
    (metrics / "experts_ms_per_step.py").write_text(
        "from bench import program_trace as pt\n\n\n"
        "def read(ctx):\n"
        '    return pt.decode_scoped_ms_per_step(ctx, ("experts",))\n')
    monkeypatch.setattr(family, "DIR", families)
    monkeypatch.setattr(harness, "METRICS_DIR", metrics)
    spec = dict(small_spec(), family="moe_toy")
    text = ('  %fusion.3 = bf16[8] fusion(%p), metadata={op_name="jit(run)/'
            'while/body/experts/dot_general"}\n'
            '  %fusion.4 = bf16[8] fusion(%fusion.3), metadata={op_name='
            '"jit(run)/while/body/mlp/mul"}\n'
            '  ROOT %copy.5 = bf16[8] copy(%fusion.4)\n')
    trace = tr.Trace(window=(0, 1000), host=[],
                     modules=[[(0, 600, "jit_run", "9")]],
                     ops=[[(0, 300, "fusion.3"), (300, 500, "fusion.4"),
                           (500, 600, "copy.5")]])
    decode = [harness.Decode(0.0, 2, [10, 20], 0.5)]
    ctx = types.SimpleNamespace(
        trace=trace, calls=harness.Calls(decode=decode),
        window_ns=[trace.window],
        scope_map=pt.scope_map(trace, decode, {2: (text, text)},
                               family.load(spec).SCOPES))
    assert harness.read_metric("experts_ms_per_step", ctx) == \
        pytest.approx(300e-6 / 2)
    # the dense family names no such scope: there the operation is unscoped
    dense = pt.scope_map(trace, decode, {2: (text, text)}, SCOPES)
    assert dense[("jit_run", "9")]["fusion.3"] == pt.UNSCOPED


@pytest.fixture(scope="module")
def recorded():
    """The recorded trace, its decode calls (one per ``serve.decode`` span
    in the window) and the scope map bound from its texts."""
    path = DATA / "trace_program"
    trace = tr.load(str(path / "trace.xplane.pb"))
    lo, hi = trace.window
    decode = [harness.Decode(a * 1e-9, s["k"], [], 0.0)
              for a, _, n, s in trace.spans
              if n == "serve.decode" and lo <= a < hi]
    texts, named = load_texts(path)
    ks = {s["k"] for _, _, n, s in trace.spans if n == "serve.decode"}
    ran, twin = by_length(texts, ks), by_length(named, ks)
    smap = pt.scope_map(trace, decode, {k: (ran[k], twin[k]) for k in ran},
                        SCOPES)
    return (trace, decode, smap,
            json.loads((path / "expected.json").read_text()))


def test_recorded_chip_trace_reduces_to_the_perfetto_numbers(recorded):
    trace, decode, smap, want = recorded
    spans, win = trace.spans, [trace.window]
    steps = sum(c.k for c in decode)
    assert steps == want["decode_steps"]
    # both fused lengths of jit_run ran, under ids of their own
    assert len({run for run in smap if run[0] == "jit_run"}) >= 2
    by_scope = pt.scope_ns(trace, pt.DECODE_PROGRAMS, win, smap)
    assert pt.coverage(by_scope) == pytest.approx(want["scope_coverage"],
                                                  rel=REL)
    assert pt.coverage(by_scope) >= pt.MIN_COVERAGE
    # the .xplane.pb reader keeps whole nanoseconds, the export picoseconds:
    # up to 1 ns less per operation, hundreds of ~50-1000 ns operations
    ops = want["device_scope_ops"]
    for scope, s in want["device_scopes_s"].items():
        assert by_scope.get(scope, 0) * 1e-9 == pytest.approx(
            s, abs=ops[scope] * 1e-9)
    for name, scopes in (("kv_cache_ms_per_step", KV),
                         ("lm_head_ms_per_step", ("lm_head",))):
        assert pt.scoped_ms(by_scope, scopes, steps) == pytest.approx(
            want[name], abs=sum(ops[s] for s in scopes) * 1e-6 / steps)
    assert pt.host_idle_ms_per_step(trace, spans, win) == pytest.approx(
        want["host_idle_ms_per_step"], rel=REL)
    gaps = dict(pt.gaps_by_program(trace, spans, win, n=100))
    assert set(gaps) == set(want["idle_by_program_s"])
    for name, s in want["idle_by_program_s"].items():
        assert gaps[name] == pytest.approx(s, rel=REL, abs=1e-9)


def test_readers_read_the_recorded_trace_through_the_context(recorded):
    trace, decode, smap, want = recorded
    cell = cells.Cell("small.open", 1, small_spec(), small_traffic(), [], [])
    win = [trace.window]
    ctx = harness.Context(cell, None, {}, harness.Calls(decode=decode),
                          trace, win, win, 0.0, scope_map=smap)
    lo, hi = trace.window
    assert ctx.spans and all(b > lo and a < hi for a, b, _, _ in ctx.spans)
    ops = want["device_scope_ops"]
    steps = want["decode_steps"]
    assert harness.read_metric("host_idle_ms_per_step", ctx) == \
        pytest.approx(want["host_idle_ms_per_step"], rel=REL)
    assert harness.read_metric("lm_head_ms_per_step", ctx) == \
        pytest.approx(want["lm_head_ms_per_step"],
                      abs=ops["lm_head"] * 1e-6 / steps)
    assert harness.read_metric("kv_cache_ms_per_step", ctx) == \
        pytest.approx(want["kv_cache_ms_per_step"],
                      abs=sum(ops[s] for s in KV) * 1e-6 / steps)
    scopes = harness.device_scopes(ctx)
    assert scopes[pt.UNMAPPED] == 0.0
    assert sum(scopes.values()) == pytest.approx(
        sum(want["device_scopes_s"].values()), abs=sum(ops.values()) * 1e-9)
