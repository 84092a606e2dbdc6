"""A whole run of the harness at a small size on the CPU, sound and broken.

``run_cell`` is everything ``bench/run.py`` does after its look for a chip.
A sound program must come out ``correct``; with the timed path broken
underneath (a decode or a prefill that hands back its cache unchanged, a
served token altered where it is produced) ``correct`` must come out false.

The small cell's limits are set like the cells' own, from readings on the
CPU over seeds 1-8 in both small cells (36-48 checked tokens) and in the
control's set-up below (96 tokens), with and without q/k norm: the program
reads at most 0.012 (widest gap) and 0.00026 (mean gap), the fp8 control
at least 0.054 and 0.0031.  Limits 0.03 and 0.001.
"""

import json
import subprocess
import sys

import numpy as np
import pytest

from bench import cells, common, correctness, family, harness
from smallcell import small_spec, small_traffic

LIMITS = {"max_logit_gap": 0.03, "mean_logit_gap": 0.001}
E2E = [{"name": n, "unit": u} for n, u in (
    ("ttft_p95_ms", "ms"), ("tpot_p90_ms", "ms"),
    ("output_tok_s", "tokens/s"), ("setup_s", "s"))]


def _cell(loop, qk_norm=False):
    return cells.Cell(f"small.{loop}", 1, small_spec(qk_norm),
                      small_traffic(loop), E2E, [])


def _run(cell, seed=4294967311, seconds=1.5):
    import time
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            limits=LIMITS)


@pytest.mark.parametrize("loop,qk_norm", [("open", False), ("closed", True)])
def test_sound_run_is_correct(loop, qk_norm):
    r = _run(_cell(loop, qk_norm))
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["checked_tokens"] >= 12
    m = r["metrics"]
    assert m["tpot_p90_ms"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert m["output_tok_s"]["value"] > 0
    json.dumps(r)


def test_traced_run_keeps_its_line_and_maps_scopes_after_the_window(
        monkeypatch):
    """A traced run on the CPU, whose trace holds no device planes: the
    decode programs' texts are taken after the window, the breakdown names
    the decode programs' time by scope (none here) and the line keeps its
    shape, ``checks`` last.  (The peaks table has no CPU; the readers here
    read none.)"""
    import time

    from bench import peaks
    v5e = peaks.peaks("TPU v5 lite")
    monkeypatch.setattr(peaks, "peaks", lambda kind: v5e)
    per = [{"name": n, "unit": "ms"} for n in (
        "host_idle_ms_per_step", "lm_head_ms_per_step")]
    cell = cells.Cell("small.open", 1, small_spec(), small_traffic("open"),
                      E2E, per)
    r = harness.run_cell(cell, 4294967311, 1.5, True, time.perf_counter(),
                         limits=LIMITS)
    assert r["correct"] is True, r["checks"]
    assert list(r)[-1] == "checks"
    assert r["scope_map_s"] > 0
    assert r["breakdown"]["device_scopes"] == [["unmapped", 0.0]]
    assert set(r["metrics"]) <= {"host_idle_ms_per_step"}
    json.dumps(r)


def _break(monkeypatch, fault):
    from repro.serve.engine import PagedTransformerModel as P
    decode, prefill = P.decode_multi, P.prefill

    if fault == "decode_state_unchanged":
        def broken(self, pool, tok, pos, k):
            _, rows, tok, pos = decode(self, pool, tok, pos, k)
            return pool, rows, tok, pos
        monkeypatch.setattr(P, "decode_multi", broken)
    elif fault == "prefill_state_unchanged":
        def broken(self, pool, prompts, slots, tok, pos):
            _, firsts, tok, pos = prefill(self, pool, prompts, slots, tok, pos)
            return pool, firsts, tok, pos
        monkeypatch.setattr(P, "prefill", broken)
    elif fault == "token_altered":
        def broken(self, pool, tok, pos, k):
            pool, rows, tok, pos = decode(self, pool, tok, pos, k)
            return pool, rows.at[-1].add(1) % self.cfg.vocab_size, tok, pos
        monkeypatch.setattr(P, "decode_multi", broken)


@pytest.mark.parametrize("fault", ["decode_state_unchanged",
                                   "prefill_state_unchanged",
                                   "token_altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    _break(monkeypatch, fault)
    r = _run(_cell("open"))
    assert r["correct"] is False
    gap = r["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_one_precision_lower_fails_the_limit():
    """The control of the check, at a small size: the reference in fp8
    puts first tokens whose float32 gap passes the limit, where the
    program's served tokens stay under it."""
    cell = _cell("open")
    dense = family.load(cell.spec)
    cfg = dense.model_config(cell.spec)
    seed = 1
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (16, 48, 48, 16)]
    from repro.serve.engine import serve_requests
    from repro.sharding.rules import Rules
    params = dense.init_weights(cfg)(common.seed_key(seed))
    rep = serve_requests(params, cfg, Rules.null(),
                         [(p, 24, 0.0) for p in prompts], n_slots=4,
                         page_size=16)
    served = [rep.completed[i] for i in range(len(prompts))]
    g = correctness.gaps(cell.spec, cfg, seed, prompts, served, 256, 128,
                         ["fp8"])
    program, control = (correctness.numbers(g[k]) for k in ("served", "fp8"))
    assert correctness.judge(program, LIMITS)[1]
    assert not correctness.judge(control, LIMITS)[1]
    assert program["max_logit_gap"] <= LIMITS["max_logit_gap"] \
        < control["max_logit_gap"]


def test_run_without_a_tpu_exits_nonzero_and_names_the_platform():
    out = subprocess.run(
        [sys.executable, str(cells.REPO / "bench/run.py"), "--workload",
         "granite_8b.chat-poisson", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert not out.stdout.strip()


def test_decode_ks_cover_every_fused_length():
    assert harness.decode_ks(256) == [1, 2, 4, 8, 16, 32, 64, 128]
    assert harness.decode_ks(16) == [1, 2, 4, 8]


def test_end_to_end_metrics_by_name():
    recs = [harness.Record(i, np.zeros(4, np.int32), 11, due=0.0,
                           first=0.1 * (i + 1), delivered=0.1 * (i + 1) + 1.0)
            for i in range(10)]
    assert harness.end_to_end("ttft_p50_ms", recs, 0, 1.0, 0.0) == \
        pytest.approx(550.0)
    assert harness.end_to_end("tpot_p90_ms", recs, 0, 1.0, 0.0) == \
        pytest.approx(100.0)
    assert harness.end_to_end("output_tok_s", recs, 500, 2.0, 0.0) == 250.0
    assert harness.end_to_end("setup_s", recs, 0, 1.0, 7.5) == 7.5
    assert harness.end_to_end("tpot_p90_ms", [], 0, 1.0, 0.0) is None
