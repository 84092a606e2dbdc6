"""Observability-plane invariants: deterministic traces, order-invariant
metric merges, plan-vs-actual drift, and the zero-added-dispatch contract.

The acceptance oracle: a 32-request staggered fleet with one replica
killed mid-decode and a later join exports a BYTE-identical Chrome trace
across two runs (every timeline is an injectable tick clock — wall time
never enters the event stream), the trace carries a requeue instant for
every request outstanding at the kill, and tracing adds zero model
dispatches over the NullTracer run.
"""

import asyncio
import json

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.fleet import (FaultPlan, FleetClosed, FleetController,
                         FleetFrontend, Replica, UnknownRequest,
                         build_engine)
from repro.obs import (DriftMonitor, Histogram, MetricsRegistry, NullTracer,
                       ProfilerTracer, Tracer, drift_fractions,
                       throughput_summary, to_chrome_json,
                       write_chrome_trace)
from repro.serve.engine import AdmissionError, EngineConfig, synthetic_workload
from repro.serve.engine.planner import CapacityPlanner
from test_serve_engine import FakeModel


def fake_workload(n, seed=0, stagger=0.5):
    return synthetic_workload(n, FakeModel.V, lens=(5, 8, 12, 16),
                              news=(2, 3, 6, 9), stagger=stagger, seed=seed)


ENGINE_CFG = dict(n_slots=4, max_prompt_len=32, max_new_cap=16,
                  cache_len=48)


def traced_fleet_run(n=32, seed=0):
    """One deterministic kill+join fleet run on a shared tracer/registry."""
    tracer, metrics = Tracer(), MetricsRegistry()
    cfg = EngineConfig(**ENGINE_CFG)
    replicas = [
        Replica("r0", FakeModel(), cfg, rate=1.0,
                fault=FaultPlan(kill_at=6), tracer=tracer, metrics=metrics),
        Replica("r1", FakeModel(), cfg, rate=2.0,
                tracer=tracer, metrics=metrics),
        Replica("r2", FakeModel(), cfg, rate=0.5,
                tracer=tracer, metrics=metrics),
    ]
    controller = FleetController(replicas, miss_threshold=3,
                                 tracer=tracer, metrics=metrics)
    controller.schedule_join(
        Replica("r3", FakeModel(), cfg, rate=1.5,
                tracer=tracer, metrics=metrics), at_tick=10)
    for p, m, a in fake_workload(n, seed):
        controller.submit(p, m, arrival=a)
    report = controller.run()
    return tracer, metrics, report, controller


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_tracer_spans_events_counters():
    clock = iter(range(100))
    tr = Tracer(clock=lambda: next(clock))
    key = tr.begin("work", track="t", lane="l", a=1)
    tr.event("mark", track="t", lane="l")
    tr.counter("depth", 3, track="t")
    tr.end(key, b=2)
    phs = [e["ph"] for e in tr.events]
    assert phs == ["B", "i", "C", "E"]
    # timestamps come from the injected clock, in call order
    assert [e["ts"] for e in tr.events] == [0.0, 1.0, 2.0, 3.0]
    assert tr.events[0]["args"] == {"a": 1}
    assert tr.events[-1]["args"] == {"b": 2}
    assert tr.open_spans() == []


def test_tracer_keyed_spans_cross_calls_and_rebegin_closes_stale():
    tr = Tracer(clock=lambda: 0.0)
    tr.begin("qw", key=("qw", 1))
    assert tr.open_spans() == ["qw"]
    # re-begin of the same key closes the stale span first
    tr.begin("qw", key=("qw", 1))
    assert [e["ph"] for e in tr.events] == ["B", "E", "B"]
    tr.end(("qw", 1))
    tr.end(("qw", 1))          # unknown key: no-op
    tr.end(("never", 9))       # never opened: no-op
    assert [e["ph"] for e in tr.events] == ["B", "E", "B", "E"]


def test_null_tracer_is_inert():
    nt = NullTracer()
    assert not nt.enabled
    with nt.span("x"):
        nt.event("y")
        nt.end(nt.begin("z"))
        nt.counter("c", 1)
    assert len(nt) == 0 and nt.events == [] and nt.open_spans() == []


def test_chrome_export_shape_and_lane_assignment():
    tr = Tracer(clock=lambda: 2.0)
    with tr.span("s", track="engine", lane="engine"):
        tr.event("e", track="engine", lane="req:0", rids=[1, 2])
    doc = json.loads(to_chrome_json(tr))
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    # one process_name per track, one thread_name per (track, lane)
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    body = [e for e in evs if e["ph"] != "M"]
    assert all(e["ts"] == 2000.0 for e in body)  # ticks -> ms -> us
    inst = next(e for e in body if e["ph"] == "i")
    assert inst["args"]["rids"] == [1, 2]        # lists survive as JSON


# ---------------------------------------------------------------------------
# the determinism oracle (acceptance)
# ---------------------------------------------------------------------------

def test_fleet_trace_byte_identical_across_runs():
    tr1, m1, rep1, _ = traced_fleet_run()
    tr2, m2, rep2, _ = traced_fleet_run()
    assert rep1.requeues >= 1 and rep1.kills and rep1.joins
    j1, j2 = to_chrome_json(tr1), to_chrome_json(tr2)
    assert len(tr1.events) > 100
    assert j1 == j2                       # byte-identical export
    # counters and gauges are tick-determined and equally deterministic;
    # histograms hold wall-clock OBSERVED VALUES (TTFT seconds) so only
    # their event counts are schedule-determined, not their bucket fill
    s1, s2 = m1.snapshot(), m2.snapshot()
    assert s1["counters"] == s2["counters"]
    assert s1["gauges"] == s2["gauges"]
    assert ({k: v["count"] for k, v in s1["histograms"].items()}
            == {k: v["count"] for k, v in s2["histograms"].items()})


def test_fleet_trace_has_requeue_event_per_outstanding_request():
    tracer, metrics, report, controller = traced_fleet_run()
    requeued_rids = sorted(e["args"]["rid"] for e in tracer.events
                           if e["name"] == "requeue")
    expect = sorted(rid for rid, fr in controller.requests.items()
                    if fr.n_requeues > 0)
    assert requeued_rids == expect and len(requeued_rids) == report.requeues
    assert metrics.counter_value("requeues") == report.requeues
    # membership events landed on the controller track
    names = {e["name"] for e in tracer.events if e["track"] == "controller"}
    assert {"kill", "join", "replan", "route"} <= names


def test_trace_file_roundtrip(tmp_path):
    tracer, _, _, _ = traced_fleet_run(n=8)
    path = write_chrome_trace(tracer, tmp_path / "trace.json")
    doc = json.loads(open(path).read())
    assert doc["traceEvents"] and doc["displayTimeUnit"] == "ms"


# ---------------------------------------------------------------------------
# zero added dispatches (acceptance)
# ---------------------------------------------------------------------------

class CountingFake(FakeModel):
    """FakeModel that counts its jit-dispatch-equivalent entry points."""

    def __init__(self):
        self.dispatches = 0

    def prefill(self, *a):
        self.dispatches += 1
        return super().prefill(*a)

    def decode_multi(self, *a, **k):
        self.dispatches += 1
        return super().decode_multi(*a, **k)


def run_counting_engine(tracer):
    model = CountingFake()
    eng = build_engine(model, EngineConfig(**ENGINE_CFG), tracer=tracer)
    for p, m, a in fake_workload(12, seed=3):
        eng.submit(p, m, arrival=a)
    rep = eng.run()
    return model.dispatches, rep


def test_tracing_adds_zero_dispatches(tmp_path):
    import jax
    d_null, rep_null = run_counting_engine(NullTracer())
    tr = Tracer()
    runs = [run_counting_engine(tr), run_counting_engine(ProfilerTracer())]
    with jax.profiler.trace(str(tmp_path)):       # annotations recording
        runs.append(run_counting_engine(ProfilerTracer()))
    for d_traced, rep_traced in runs:
        assert d_traced == d_null
        assert rep_traced.steps == rep_null.steps
        for rid in rep_null.completed:
            np.testing.assert_array_equal(rep_null.completed[rid],
                                          rep_traced.completed[rid])
    assert len(tr.events) > 0


def test_profiler_tracer_hooks_become_annotations(tmp_path):
    import jax
    from jax.profiler import ProfileData
    tr = ProfilerTracer()
    assert tr.enabled and tr.clock is None
    with jax.profiler.trace(str(tmp_path)):
        key = tr.begin("request", track="e", lane="req:1", key=("r", 1),
                       rid=1)
        with tr.span("step", track="e", lane="engine", step=3):
            tr.event("retire", rid=1)
            tr.counter("queue_depth", 2, track="e")
        assert tr.open_spans() == ["request"]
        tr.end(key, tokens=7)
        tr.end(key)                               # closed: no-op
    assert tr.open_spans() == []
    (path,) = tmp_path.glob("**/*.xplane.pb")
    got = {e.name: dict(e.stats)
           for plane in ProfileData.from_file(str(path)).planes
           for line in plane.lines for e in line.events
           if e.name.startswith("serve.")}
    assert got == {"serve.request": {"rid": 1, "tokens": 7},
                   "serve.step": {"step": 3},
                   "serve.retire": {"rid": 1},
                   "serve.queue_depth": {"value": 2}}


# ---------------------------------------------------------------------------
# engine instrumentation
# ---------------------------------------------------------------------------

def test_engine_spans_and_rejection_metrics():
    tr, reg = Tracer(), MetricsRegistry()
    eng = build_engine(FakeModel(), EngineConfig(**ENGINE_CFG),
                       tracer=tr, metrics=reg)
    rid = eng.submit(np.arange(1, 6), 4)
    with pytest.raises(AdmissionError):
        eng.submit(np.zeros(99, np.int32), 1)          # prompt too long
    with pytest.raises(AdmissionError):
        eng.submit(np.arange(1, 6), 0)                 # max_new < 1
    eng.run()
    assert reg.counter_value("admission_rejections", reason="prompt_len") == 1
    assert reg.counter_value("admission_rejections", reason="max_new") == 1
    assert reg.counter_total("admission_rejections") == 2
    assert reg.counter_value("requests_submitted") == 1
    assert reg.counter_value("requests_retired") == 1
    names = [(e["ph"], e["name"]) for e in tr.events
             if e["lane"] == f"req:{rid}"]
    # queue-wait opens at submit, closes at admit; request spans
    # admit->retire
    assert names[0] == ("B", "queue_wait")
    assert ("E", "queue_wait") in names and ("B", "request") in names
    assert names[-2:] == [("E", "request"), ("i", "retire")]
    # TTFT is observed into the fixed-bucket histogram
    assert reg.histogram("ttft_s").n == 1
    snap = reg.snapshot()
    assert "queue_depth" in snap["gauges"]
    assert "pool_occupancy" in snap["gauges"]


def test_engine_report_as_dict_matches_throughput_summary():
    eng = build_engine(FakeModel(), EngineConfig(**ENGINE_CFG))
    for p, m, a in fake_workload(8, seed=1):
        eng.submit(p, m, arrival=a)
    rep = eng.run()
    d = rep.as_dict()
    ref = throughput_summary(
        useful_tokens=rep.total_tokens, wall_s=rep.wall,
        ttfts_s=rep.ttft.values(),
        occupancy_sum=rep.occupancy * rep.decode_steps,
        decode_steps=rep.decode_steps)
    for k, v in ref.items():
        assert d[k] == v, k
    assert d["tokens_per_sec"] == rep.tokens_per_sec
    assert d["ttft_mean_s"] == pytest.approx(rep.ttft_mean)
    assert d["occupancy"] == pytest.approx(rep.occupancy)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counters_gauges_labels():
    reg = MetricsRegistry()
    reg.counter("rej", reason="full").inc()
    reg.counter("rej", reason="full").inc(2)
    reg.counter("rej", reason="len").inc()
    reg.gauge("depth").set(7)
    assert reg.counter_value("rej", reason="full") == 3
    assert reg.counter_total("rej") == 4
    snap = reg.snapshot()
    assert snap["counters"]["rej{reason=full}"] == 3
    assert snap["gauges"]["depth"] == 7.0
    with pytest.raises(ValueError):
        reg.counter("rej").inc(-1)


def test_histogram_buckets_and_edge_validation():
    h = Histogram(edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 3.0, 100.0):
        h.observe(v)
    # (-inf,1] (1,2] (2,4] (4,inf) -- bisect_left puts v==edge in the
    # bucket left of the edge
    assert h.counts == [2, 1, 1, 1]
    assert h.n == 5 and h.mean == pytest.approx(106.0 / 5)
    with pytest.raises(ValueError):
        Histogram(edges=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram(edges=())
    with pytest.raises(ValueError):
        h.merge(Histogram(edges=(1.0, 2.0)))
    reg = MetricsRegistry()
    reg.histogram("h", edges=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("h", edges=(1.0, 3.0))   # redeclare with new edges
    with pytest.raises(ValueError):
        reg.histogram("fresh")                 # first use needs edges


@settings(max_examples=60, deadline=None)
@given(st.lists(st.lists(st.integers(0, 1000), max_size=20), max_size=8),
       st.integers(0, 2**31))
def test_histogram_merge_is_order_invariant(partials, seed):
    """Merging per-replica partial histograms in ANY order yields the
    identical fleet histogram (integer counts + integer-valued totals)."""
    import random
    edges = (10.0, 100.0, 500.0)

    def merged(order):
        acc = Histogram(edges)
        for obs in order:
            part = Histogram(edges)
            for v in obs:
                part.observe(v)
            acc.merge(part)
        return acc.snapshot()

    shuffled = list(partials)
    random.Random(seed).shuffle(shuffled)
    assert merged(shuffled) == merged(partials)


# ---------------------------------------------------------------------------
# plan-vs-actual drift
# ---------------------------------------------------------------------------

def test_drift_fractions_normalized_by_makespan():
    d = drift_fractions([10.0, 5.0], [12.0, 5.0])
    np.testing.assert_allclose(d, [0.2, 0.0])
    with pytest.raises(ValueError):
        drift_fractions([1.0], [1.0, 2.0])


def test_undisturbed_star_run_within_quantum_tolerance():
    """Acceptance: an undisturbed run — every node serving exactly the
    real-valued equal-finish optimum at its true speed — drifts from the
    integer plan by no more than the integer-adjustment quantum prices."""
    reg = MetricsRegistry()
    planner = CapacityPlanner(rates=[1.0, 2.0, 0.5, 1.5], quantum=1)
    plan = planner.plan(200).partition
    mon = DriftMonitor(plan, metrics=reg, gauge_name="plan_drift")
    assert (plan.k > 0).all()
    per_unit = plan.finish_times / plan.k
    observed = plan.k_real * per_unit     # the equal-finish optimum
    drift = mon.observe_finish(observed)
    assert drift <= mon.tolerance() + 1e-12
    assert not mon.should_replan()
    assert reg.snapshot()["gauges"]["plan_drift"] == pytest.approx(drift)
    # a genuinely disturbed run (one node 2x slower) must trip the trigger
    slow = observed.copy()
    slow[0] = 2.0 * plan.finish_times[0]
    mon.observe_finish(slow)
    assert mon.should_replan()


def test_drift_observe_shares_serving_plane():
    plan = CapacityPlanner(rates=[1.0, 3.0], quantum=1).plan(100).partition
    mon = DriftMonitor(plan)
    # serving exactly the planned fractions -> zero drift
    assert mon.observe_shares(plan.k.astype(float)) == pytest.approx(0.0)
    with pytest.raises(ValueError):
        mon.observe_shares([1.0])


def test_fleet_drift_gauge_present_and_bounded():
    _, metrics, _, _ = traced_fleet_run()
    snap = metrics.snapshot()
    assert "fleet_drift" in snap["gauges"]
    assert 0.0 <= snap["gauges"]["fleet_drift"] <= 1.0


# ---------------------------------------------------------------------------
# frontend error paths (satellite: defined exceptions, no hangs)
# ---------------------------------------------------------------------------

def frontend_fixture():
    cfg = EngineConfig(**ENGINE_CFG)
    controller = FleetController(
        [Replica("r0", FakeModel(), cfg, rate=1.0)])
    return FleetFrontend(controller, max_pending=8)


def test_stream_unknown_rid_raises():
    fe = frontend_fixture()

    async def go():
        with pytest.raises(UnknownRequest):
            async for _ in fe.stream(404):
                pass
    asyncio.run(go())


def test_submit_after_drain_raises_fleet_closed():
    fe = frontend_fixture()

    async def go():
        rid = await fe.submit(np.arange(1, 6), 3)
        report = await fe.drain()
        assert rid in report.completed
        with pytest.raises(FleetClosed):
            await fe.submit(np.arange(1, 6), 3)
        # streaming a completed rid after drain still works (results are
        # final) — only NEW work is refused
        got = [t async for t in fe.stream(rid)]
        assert np.array_equal(got, report.completed[rid])
    asyncio.run(go())
