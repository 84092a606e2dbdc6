"""The trace reduction on a small trace recorded on a TPU v5e.

``bench/testdata/trace`` holds the profiler's ``.xplane.pb`` of eight short
requests served at a small size (``bench/record_trace.py``) and
``expected.json``: the same numbers worked out from the profiler's Perfetto
JSON export of that trace, which the reduction never reads.  The export
keeps each event's times to the picosecond and the ``.xplane.pb`` reader to
the nanosecond, so sums over thousands of events agree to 1e-3.
"""

import json
import pathlib

import pytest

from bench import trace as tr

DATA = pathlib.Path(__file__).resolve().parents[2] / "bench/testdata/trace"
NS = 1e-9
REL = 1e-3


@pytest.fixture(scope="module")
def recorded():
    return (tr.load(str(DATA / "trace.xplane.pb")),
            json.loads((DATA / "expected.json").read_text()))


def test_window_and_busy_time(recorded):
    t, want = recorded
    assert len(t.modules) == len(t.ops) == 1
    win = [t.window]
    assert (t.window[1] - t.window[0]) * NS == pytest.approx(
        want["window_s"], rel=REL)
    assert tr.busy_ns(t, win) * NS == pytest.approx(want["busy_s"], rel=REL)


@pytest.mark.parametrize("program", ["jit_paged_group_prefill",
                                     "jit_paged_decode1", "jit_run"])
def test_time_per_program(recorded, program):
    t, want = recorded
    assert tr.module_ns(t, [program], [t.window]) * NS == pytest.approx(
        want["programs"][program]["seconds"], rel=REL)


def test_seconds_per_program(recorded):
    t, want = recorded
    got = tr.program_seconds(t, [t.window])
    for program, w in want["programs"].items():
        assert got[program] == pytest.approx(w["seconds"], rel=REL)
    assert list(got.values()) == sorted(got.values(), reverse=True)


def test_idle_time_by_host_span(recorded):
    t, want = recorded
    got = dict(tr.gaps_by_host(t, [t.window], n=100))
    assert set(got) == set(want["idle_by_host_s"])
    for name, s in want["idle_by_host_s"].items():
        assert got[name] == pytest.approx(s, rel=REL)
    idle = sum(b - a for a, b in tr.idle_gaps(t, [t.window])) * NS
    assert idle + want["busy_s"] == pytest.approx(want["window_s"], rel=REL)


def test_top_ops_add_up_to_no_more_than_busy(recorded):
    t, want = recorded
    ops = tr.top_ops(t, [t.window], n=10)
    assert len(ops) == 10
    assert all(name.split("/")[0].startswith("jit_") for name, _ in ops)
    assert ops == sorted(ops, key=lambda x: -x[1])


def test_interval_helpers():
    assert tr.merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert tr.overlap([(0, 3), (5, 6)], [(2, 5.5)]) == 1.5
    c = tr.Clip([(0, 10), (20, 30)])
    assert c.length(5, 25) == 10 and c.length(11, 19) == 0
