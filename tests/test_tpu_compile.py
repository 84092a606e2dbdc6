"""Compile-only checks for a described TPU v5e (no chip attached).

The TPU compiler is installed even where no chip is, so these tests lower
and compile the serving path's programs at llama3_2_3b's published widths
for one v5e chip, the paper's k-sharded matmul on a 2x2 v5e mesh, and the
Pallas kernels in compiled (not interpreted) mode.  The compiler refuses
what the chip would refuse: a program that does not fit 16 GB of HBM, a
kernel block that does not tile, a sharding it cannot partition.

Nothing here runs a program.  The topology, shardings and shapes are built
inside module fixtures (never at import): only the process that runs these
tests may load the TPU library, and every test-runner worker must collect
the same tests.
"""

import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.compat import make_mesh
from repro.core.lbp_matmul import lbp_matmul
from repro.kernels import ops
from repro.launch.serve import param_init, serving_config
from repro.models import transformer as T
from repro.serve import PagedTransformerModel
from repro.sharding.rules import Rules

HBM_BYTES = 16 * 10**9          # one v5e chip (Google Cloud, "TPU v5e")
N_SLOTS, CACHE_LEN, PAGE = 8, 1024, 16
PAGES_PER_SLOT = CACHE_LEN // PAGE
N_PAGES = N_SLOTS * PAGES_PER_SLOT


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


@pytest.fixture(scope="module")
def llama(one_chip):
    """llama3_2_3b as the launcher serves it on one chip: published
    widths, tp=1, bf16 weights; shapes only."""
    rules = Rules.null()
    cfg = serving_config("llama3_2_3b", demo=False, rules=rules)
    assert (cfg.tp, cfg.h_padded, cfg.d_model) == (1, 24, 3072)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    params = _placed(jax.eval_shape(param_init(cfg), key), one_chip)
    pool = _placed(jax.eval_shape(
        lambda: T.init_cache(cfg, N_PAGES + 1, PAGE)), one_chip)
    model = PagedTransformerModel(None, cfg, rules)
    return dict(cfg=cfg, key=key, params=params, pool=pool, model=model,
                table=_i32((N_SLOTS, PAGES_PER_SLOT), one_chip),
                vec=_i32((N_SLOTS,), one_chip))


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return mem


def test_param_init_emits_bf16(llama):
    compiled = param_init(llama["cfg"]).lower(llama["key"]).compile()
    mem = _fits(compiled)
    leaves = jax.tree_util.tree_leaves(llama["params"])
    assert {a.dtype for a in leaves} == {jnp.dtype(jnp.bfloat16)}
    n_bytes = 2 * sum(a.size for a in leaves)
    # bf16 bytes plus the chip's per-buffer alignment; a float32 tree
    # would be twice as large
    assert n_bytes <= mem.output_size_in_bytes < 1.001 * n_bytes
    assert n_bytes < 7 * 2**30


def _reads_pages_in_place(compiled, llama):
    """The paged decode kernel is in the program, and no buffer has the
    per-slot view's shape (L, n_slots, view_len, KV, hd)."""
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "paged_decode_attention" in hlo
    L, _, _, KV, hd = llama["pool"]["k"].shape
    assert f"[{L},{N_SLOTS},{CACHE_LEN},{KV},{hd}]" not in hlo


def _updates_pool_in_place(compiled, llama):
    """The program aliases its output to the whole donated pool, and
    copies no array of a pool leaf's shape."""
    leaves = jax.tree_util.tree_leaves(llama["pool"])
    pool_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    hlo = compiled.as_text()
    for shape in {a.shape for a in leaves}:
        dims = ",".join(map(str, shape))
        copies = re.findall(rf"= \w+\[{dims}\]\S* copy\(", hlo)
        assert not copies, copies


def test_paged_decode_step(llama):
    m = llama["model"]
    compiled = m._paged_decode1.lower(
        llama["params"], llama["vec"], llama["vec"], llama["pool"],
        llama["table"], llama["table"]).compile()
    _fits(compiled)
    _reads_pages_in_place(compiled, llama)
    _updates_pool_in_place(compiled, llama)


def test_paged_decode_multi_scan(llama):
    fn = llama["model"].paged_decode_scan(4)
    compiled = fn.lower(llama["params"], llama["vec"], llama["vec"],
                        llama["pool"], llama["table"],
                        llama["table"]).compile()
    _fits(compiled)
    _reads_pages_in_place(compiled, llama)
    _updates_pool_in_place(compiled, llama)


def test_paged_group_prefill(llama, one_chip):
    B, S = 2, 512
    compiled = llama["model"]._paged_prefill.lower(
        CACHE_LEN, llama["params"], _i32((B, S), one_chip),
        _i32((B,), one_chip), _i32((B,), one_chip),
        _i32((B, PAGES_PER_SLOT), one_chip), llama["pool"], llama["vec"],
        llama["vec"]).compile()
    _fits(compiled)
    _updates_pool_in_place(compiled, llama)


@pytest.mark.parametrize("mode", ["layers", "allreduce", "scatter"])
def test_lbp_matmul_on_2x2_mesh(topo, mode):
    """The down-projection shape, k-sharded over the four chips."""
    mesh = make_mesh((4,), ("model",), devices=topo.devices)
    x = jax.ShapeDtypeStruct((4096, 8192), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P(None, "model")))
    w = jax.ShapeDtypeStruct((8192, 3072), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("model", None)))
    compiled = jax.jit(lambda x, w: lbp_matmul(x, w, mesh, mode=mode)
                       ).lower(x, w).compile()
    hlo = compiled.as_text()
    expect = {"layers": None, "allreduce": "all-reduce",
              "scatter": "reduce-scatter"}[mode]
    if expect is None:
        assert "all-reduce" not in hlo and "reduce-scatter" not in hlo
    else:
        assert expect in hlo


def test_pallas_matmul_compiles(one_chip):
    x = jax.ShapeDtypeStruct((4096, 8192), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8192, 3072), jnp.bfloat16, sharding=one_chip)
    hlo = ops.matmul.lower(x, w, interpret=False).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_pallas_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, 24, 1024, 128), jnp.bfloat16,
                             sharding=one_chip)
    hlo = ops.flash_attention.lower(q, q, q, interpret=False
                                    ).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("G", [4, 5])          # granite's and qwen3's GQA
def test_pallas_paged_decode_attention_compiles(one_chip, G):
    """The paged decode kernel alone at a cell's widths: 8 KV heads of
    128, 16-token pages, 16 rows of 112 pages, a 10-layer pool."""
    from repro.kernels.paged_decode_attention_kernel import (
        paged_decode_attention_pallas)
    pool = jax.ShapeDtypeStruct((10, 1793, 16, 8, 128), jnp.bfloat16,
                                sharding=one_chip)
    q = jax.ShapeDtypeStruct((16, 8, G, 128), jnp.bfloat16,
                             sharding=one_chip)
    hlo = jax.jit(paged_decode_attention_pallas).lower(
        q, pool, pool, _i32((), one_chip), _i32((16, 112), one_chip),
        _i32((16,), one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo
