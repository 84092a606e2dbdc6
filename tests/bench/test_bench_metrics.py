"""The per-layer metrics' readers, on the recorded chip trace and on calls
whose answers are known by hand."""

import json
import pathlib

import pytest

from bench import family, harness, peaks
from bench import trace as tr
from smallcell import small_spec, small_traffic

dense = family.load(small_spec())

REL = 1e-3   # see test_bench_trace: the export keeps picoseconds
DATA = pathlib.Path(__file__).resolve().parents[2] / "bench/testdata/trace"


@pytest.fixture(scope="module")
def ctx():
    from bench import cells
    t = tr.load(str(DATA / "trace.xplane.pb"))
    cell = cells.Cell("small.open", 1, small_spec(), small_traffic(), [], [])
    cfg = dense.model_config(cell.spec)
    calls = harness.Calls(
        decode=[harness.Decode(0.0, 1, [20, 40, 60], 0.25),
                harness.Decode(0.1, 4, [21, 41], 0.5)],
        prefill=[(0.0, [16, 48]), (0.2, [16])])
    win = [t.window]
    served_s = (t.window[1] - t.window[0]) * 1e-9
    return harness.Context(cell, cfg, peaks.peaks("TPU v5 lite"), calls, t,
                           win, win, served_s)


def _expected():
    return json.loads((DATA / "expected.json").read_text())


def test_occupancies_by_hand(ctx):
    # 1 step with 3 of 4 rows live, 4 steps with 2 of 4
    assert harness.read_metric("batch_occupancy", ctx) == pytest.approx(
        100 * (3 + 4 * 2) / (5 * 4))
    assert harness.read_metric("page_occupancy", ctx) == pytest.approx(
        100 * (0.25 + 4 * 0.5) / 5)


def test_device_times_per_unit_of_work(ctx):
    p = _expected()["programs"]
    dec_s = p["jit_paged_decode1"]["seconds"] + p["jit_run"]["seconds"]
    assert harness.read_metric("decode_step_ms", ctx) == pytest.approx(
        dec_s * 1e3 / 5, rel=REL)
    assert harness.read_metric("prefill_ms_per_ktok", ctx) == pytest.approx(
        p["jit_paged_group_prefill"]["seconds"] * 1e3 / (80 / 1000), rel=REL)


def test_roofline_and_mfu_from_costs(ctx):
    cfg, pk = ctx.cfg, ctx.peaks
    p = _expected()["programs"]
    dec_s = p["jit_paged_decode1"]["seconds"] + p["jit_run"]["seconds"]
    # one step of a call: a record with k=1; the k=4 call's
    # steps attend one position further each
    step = lambda depths: harness.Decode(0.0, 1, depths, 0.0)
    need = dense.decode_step_bytes(cfg, step([20, 40, 60])) + sum(
        dense.decode_step_bytes(cfg, step([21 + i, 41 + i]))
        for i in range(4))
    assert harness.read_metric("decode_hbm_roofline", ctx) == pytest.approx(
        100 * need / pk["hbm_bytes_per_s"] / dec_s, rel=REL)
    flops = (dense.decode_flops(cfg, step([20, 40, 60]))
             + sum(dense.decode_flops(cfg, step([21 + i, 41 + i]))
                   for i in range(4))
             + 2 * dense.prefill_flops(cfg, 16) + dense.prefill_flops(cfg, 48))
    assert harness.read_metric("mfu", ctx) == pytest.approx(
        100 * flops / (ctx.served_s * pk["bf16_flops"]))


def test_idle_share_is_what_busy_leaves(ctx):
    e = _expected()
    assert harness.read_metric("device_idle_share", ctx) == pytest.approx(
        100 * (1 - e["busy_s"] / e["window_s"]), rel=REL)


def test_costs_by_hand():
    cfg = dense.model_config(small_spec())
    d, hd, ff, V, L = 128, 32, 256, 512, 2
    per_layer = d * 4 * hd * 2 + d * 2 * hd * 2 + 3 * d * ff
    assert dense.layer_params(cfg) == per_layer
    assert dense.weight_bytes(cfg) == 2 * (L * (per_layer + 2 * d)
                                           + V * d + d)
    assert dense.kv_bytes_per_position(cfg) == 2 * 2 * L * 2 * hd
    assert dense.prefill_flops(cfg, 3) == (
        2 * L * per_layer * 3 + 4 * 4 * hd * 6 * L + 2 * V * d)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
