"""The traffic generator, and cells found by name from data files alone."""

import collections
import json
import shutil

import numpy as np
import pytest

from bench import cells, traffic
from smallcell import small_traffic

MIXES = sorted(p.stem for p in cells.TRAFFIC_DIR.glob("*.json"))


def _mix(name):
    return json.loads((cells.TRAFFIC_DIR / f"{name}.json").read_text())


def _same(a, b):
    return all(x.idx == y.idx and x.max_new == y.max_new and x.due == y.due
               and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_open_schedule_repeats_for_a_seed():
    mix = small_traffic("open")
    a = traffic.open_schedule(mix, 10.0, 2**40 + 3, 512)
    b = traffic.open_schedule(mix, 10.0, 2**40 + 3, 512)
    c = traffic.open_schedule(mix, 10.0, 2**40 + 4, 512)
    assert len(a) == len(b) == len(c) == 60 and _same(a, b)
    assert not _same(a, c)


def test_poisson_arrivals_repeat_for_a_seed_and_have_exponential_gaps():
    mix = dict(small_traffic("open"), arrivals="poisson", rate_per_s=5.0)
    a = traffic.open_schedule(mix, 400.0, 2**40 + 3, 512)
    b = traffic.open_schedule(mix, 400.0, 2**40 + 3, 512)
    c = traffic.open_schedule(mix, 400.0, 2**40 + 4, 512)
    assert _same(a, b) and len(a) != len(c)
    due = np.array([r.due for r in a])
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert 0.0 < due[0] and due[-1] < 400.0 and (gaps > 0).all()
    assert abs(len(a) - 2000) < 4 * 2000 ** 0.5
    assert abs(gaps.mean() * 5.0 - 1) < 0.1          # exponential: mean 1/rate,
    assert abs(gaps.std() / gaps.mean() - 1) < 0.1   # spread equal to the mean


def test_closed_stream_repeats_for_a_seed():
    a, b = (traffic.ClosedStream(small_traffic("closed"), 9, 512)
            for _ in range(2))
    assert _same([a.take() for _ in range(40)], [b.take() for _ in range(40)])


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_traffic_file(name):
    mix = _mix(name)
    block = mix["block"]
    reqs = traffic.requests(mix, 17, 10 * block, 1000)
    for key, got in (("prompt_tokens", [len(r.prompt) for r in reqs]),
                     ("output_tokens", [r.max_new for r in reqs])):
        dist = mix[key]
        counts = collections.Counter(got)
        for v, p in zip(dist["values"], dist["p"]):
            assert counts[v] == round(10 * block * p)
        first = collections.Counter(got[:block])   # every block is exact
        assert all(first[v] == round(block * p)
                   for v, p in zip(dist["values"], dist["p"]))


def test_every_seed_offers_the_same_sizes_and_gaps():
    mix = _mix("chat-poisson")
    a = traffic.open_schedule(mix, 30.0, 1, 1000)
    b = traffic.open_schedule(mix, 30.0, 2, 1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    # the gaps are one shuffled set; the last gap of each order falls
    # after the window, so the gaps seen between arrivals differ by one
    gaps = lambda s: collections.Counter(np.round(np.diff([r.due for r in s]),
                                                  9))
    assert sum((gaps(a) - gaps(b)).values()) <= 1
    assert 0.0 == a[0].due < a[-1].due < 30.0


def test_open_loop_rate_fills_the_window():
    mix = dict(small_traffic("open"), rate_per_s=4.5)
    reqs = traffic.open_schedule(mix, 20.0, 5, 512)
    assert len(reqs) == 90
    assert all(0 <= r.prompt.min() and r.prompt.max() < 512 for r in reqs)


def test_shares_that_do_not_split_a_block_are_refused():
    with pytest.raises(ValueError):
        traffic.block_counts({"values": [1, 2], "p": [0.3, 0.7]}, 4)


def test_a_new_cell_is_found_from_new_files_only(tmp_path):
    """A later cell adds a traffic file, a configuration file and entries in
    BENCHMARK.json; no existing file of the benchmark changes."""
    root = tmp_path / "checkout"
    shutil.copytree(cells.REPO / "bench", root / "bench")
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    mix = dict(small_traffic("open"), name="burst-test")
    (root / "bench/traffic/burst-test.json").write_text(json.dumps(mix))
    spec = json.loads((root / "bench/configs/granite_8b.json").read_text())
    (root / "bench/configs/new_model.json").write_text(
        json.dumps(dict(spec, name="new_model")))
    bench["configs"].append({"name": "new_model", "source": "x",
                             "file": "bench/configs/new_model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new_model.burst-test",
                               "config": "new_model",
                               "traffic": "burst-test", "chips": 1,
                               "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load_cell("new_model.burst-test", root)
    assert cell.traffic["name"] == "burst-test"
    assert cell.spec["name"] == "new_model"
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p90_ms", "setup_s"}
    assert all(p.read_bytes() == b for p, b in before.items())
    with pytest.raises(KeyError):
        cells.load_cell("no_such.cell", root)


def test_cells_report_what_the_contract_asks():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        assert cell.engine_config().pool_len == cell.max_prompt + cell.max_output
