"""Model families, found by the configuration's ``family``.

The dense family's weights, reference logits and operation and byte counts
are held to what ``bench/model.py``, ``bench/reference.py`` and
``bench/costs.py`` gave before the dense code moved to
``bench/families/dense.py``: digests and counts recorded from those
modules, reproduced exactly.  Then a family that is one new file, and a
family that has none.
"""

import hashlib
import json

import numpy as np
import pytest

import jax

from bench import cells, common, correctness, family, harness
from smallcell import small_spec, small_traffic

dense = family.load(small_spec())

# sha256 of every leaf's bits in the tree's order, seed 2**33 + 7
WEIGHTS = {
    False: "8b5442f8f81ec8dfb5b042e0306290c9d9bcb1394e1a88b7a472abc27ffe85a7",
    True: "b54a19b14d84285c727ba5b1ab5bd2f1ac59b7c0d724527bffa06930b5006f9a",
}
# sha256 of the float32 logits, seed 2**40 + 3: as XLA's CPU backend sums
# on several cores, and on one (the two differ in summation order alone)
LOGITS = {
    (False, None): (
        "df48a4ff22d17be73f046b94d72005515811610454505b555643ac8f3a5bb232",
        "a5b1668edcbd20f1318b490687d9ed823226381f5cdabcc6772225bc26f98906"),
    (True, None): (
        "32b5ecb74bedf3b2413d66612224ba464a0319a517bec6193678758719962361",
        "37549af7bd7606d2d18b2ffbd823241de73cf184638518dd43612a851dbbb5f7"),
    (True, "fp8"): (
        "3d9950c043faf53514486d67bc3602151fd342306ca5750318f07b335507e5ad",),
    (True, "int8"): (
        "a5bdeb5d9559652fec693d5fb6a96bcdf3c3f5dfb3e5f982f4b1dc6b83b0bc8b",
        "d0f5800bca8123f4511b82b9f215c57b7722f0ac13c707a5758828ef24108fb4"),
    (False, "w8a16"): (
        "d4545cb3f9824fe48eb03a8307ec63ca4c7d7c8b5dea66feb5f42b74b592ce00",),
}
# depths [100, 900, 1788]; k = 4 is one call of four steps
COSTS = {
    "granite_8b": {
        "weight_bytes": 8254693376,
        "decode_step_bytes_k1": 8460247040,
        "decode_step_bytes_k4": 33842315264,
        "decode_flops_k1": 25585385472.0,
        "decode_flops_k4": 102346850304.0,
        "prefill_flops": [755542523904.0, 12408789663744.0]},
    "qwen3_14b": {
        "weight_bytes": 8162073600,
        "decode_step_bytes_k1": 8276270080,
        "decode_step_bytes_k4": 33105817600,
        "decode_flops_k1": 25056542720.0,
        "decode_flops_k4": 100229857280.0,
        "prefill_flops": [636688138240.0, 10390165258240.0]},
}
# the small cell's limits (tests/bench/test_bench_harness.py)
LIMITS = {"max_logit_gap": 0.03, "mean_logit_gap": 0.001}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(a.view(np.uint16 if a.dtype.itemsize == 2
                        else np.uint32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("qk_norm", [False, True])
def test_dense_weights_are_the_parents(qk_norm):
    cfg = dense.model_config(small_spec(qk_norm=qk_norm))
    params = dense.init_weights(cfg)(common.seed_key(2**33 + 7))
    assert _digest(jax.tree_util.tree_leaves(params)) == WEIGHTS[qk_norm]


@pytest.mark.parametrize("qk_norm,quant", list(LOGITS))
def test_dense_reference_logits_are_the_parents(qk_norm, quant):
    cfg = dense.model_config(small_spec(qk_norm=qk_norm))
    rng = np.random.default_rng(7)
    a = rng.integers(0, 512, 30).astype(np.int32)
    b = rng.integers(0, 512, 50).astype(np.int32)
    rows = np.zeros(8, np.int32)
    rows[:5] = [0, 17, 29, 30 + 25, 30 + 49]
    lg = dense.reference_logits(cfg, 2**40 + 3, [a, b], rows, 256,
                                quant=quant)
    assert _digest([lg]) in LOGITS[(qk_norm, quant)]


@pytest.mark.parametrize("name", sorted(COSTS))
def test_dense_costs_are_the_parents(name):
    spec = json.loads((cells.REPO / "bench/configs" / f"{name}.json")
                      .read_text())
    fam = family.load(spec)
    cfg = fam.model_config(spec)
    call = lambda k: harness.Decode(0.0, k, [100, 900, 1788], 0.5)
    got = {"weight_bytes": fam.weight_bytes(cfg),
           "decode_step_bytes_k1": fam.decode_step_bytes(cfg, call(1)),
           "decode_step_bytes_k4": fam.decode_step_bytes(cfg, call(4)),
           "decode_flops_k1": fam.decode_flops(cfg, call(1)),
           "decode_flops_k4": fam.decode_flops(cfg, call(4)),
           "prefill_flops": [fam.prefill_flops(cfg, n) for n in (96, 1536)]}
    assert got == COSTS[name]


def test_a_family_is_one_new_file(tmp_path, monkeypatch):
    """A family file in the families' directory is all the harness needs:
    here the dense family with its tensors under ids from 13 up, built by
    ``harness.build``, served, and checked against its own reference."""
    toy = (family.DIR / "dense.py").read_text() + (
        "\nIDS = {name: i + 13 for name, i in IDS.items()}\n")
    (tmp_path / "toy.py").write_text(toy)
    monkeypatch.setattr(family, "DIR", tmp_path)
    spec = dict(small_spec(), family="toy")
    cell = cells.Cell("toy.open", 1, spec, small_traffic(), [], [])
    seed = 3
    cfg, adapter, engine = harness.build(cell, seed)
    assert family.load(spec).__file__ == str(tmp_path / "toy.py")
    theirs = dense.init_weights(cfg)(common.seed_key(seed))
    assert not np.array_equal(np.asarray(adapter.params["embed"]),
                              np.asarray(theirs["embed"]))

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (16, 48)]
    rids = [engine.submit(p, 12) for p in prompts]
    served = {}
    while engine.has_work:
        engine.step()
        served.update(engine.harvest())
    g = correctness.gaps(spec, cfg, seed, prompts,
                         [np.asarray(served[r]) for r in rids], 256, 64)
    _, ok = correctness.judge(correctness.numbers(g["served"]), LIMITS)
    assert ok


def test_an_unknown_family_names_the_file_it_looked_for():
    with pytest.raises(FileNotFoundError) as e:
        family.load(dict(small_spec(), family="no_such_family"))
    assert str(family.DIR / "no_such_family.py") in str(e.value)
