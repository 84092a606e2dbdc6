"""repro.compat (version-adaptive jax surface) + core.collectives registry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import collectives


# ---------------------------------------------------------------------------
# compat: the installed jax's API through the one import point
# ---------------------------------------------------------------------------

def test_cost_analysis_normalizes_shapes():
    class _C:
        def __init__(self, ret):
            self._ret = ret

        def cost_analysis(self):
            if isinstance(self._ret, Exception):
                raise self._ret
            return self._ret

    assert compat.cost_analysis(_C({"flops": 2.0})) == {"flops": 2.0}
    assert compat.cost_analysis(_C(None)) == {}
    assert compat.cost_analysis(_C(RuntimeError("no cost model"))) == {}


def test_shard_map_resolves_and_runs():
    mesh = compat.make_mesh((1,), ("data",))

    def local(xl):
        return jax.lax.psum(xl * 2.0, "data")

    fn = compat.shard_map(local, mesh=mesh, in_specs=P(None),
                          out_specs=P(None), check_vma=False)
    got = fn(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(got), np.arange(4.0) * 2.0)


def test_make_mesh_basic():
    mesh = compat.make_mesh((1,), ("data",), axis_types="auto")
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == 1
    assert mesh.axis_types == (compat.AxisType.Auto,)
    with pytest.raises(ValueError):
        compat.make_mesh((1,), ("data",), axis_types="bogus")


# ---------------------------------------------------------------------------
# collectives registry
# ---------------------------------------------------------------------------

def test_builtin_modes_registered():
    assert set(collectives.available_modes()) >= {
        "layers", "allreduce", "scatter"}


def test_scatter_bytes_half_of_allreduce():
    """Paper §1.2 lazy aggregation: reduce-scatter moves exactly half the
    ring bytes of all-reduce, for every (size, p, itemsize)."""
    for out_elems in (1, 4096, 1 << 20):
        for p in (2, 4, 8, 64):
            for itemsize in (1, 2, 4):
                ar = collectives.collective_bytes_per_device(
                    out_elems, p, "allreduce", itemsize)
                rs = collectives.collective_bytes_per_device(
                    out_elems, p, "scatter", itemsize)
                ly = collectives.collective_bytes_per_device(
                    out_elems, p, "layers", itemsize)
                assert ly == 0.0
                assert ar > 0.0
                assert rs == pytest.approx(0.5 * ar)


def test_bytes_table_query():
    table = collectives.bytes_table(1024, p=8, itemsize=2)
    assert table["layers"] == 0.0
    assert table["scatter"] == pytest.approx(0.5 * table["allreduce"])


def test_unknown_mode_lists_available():
    with pytest.raises(ValueError, match="registered"):
        collectives.get_mode("warp-drive")
    with pytest.raises(ValueError):
        collectives.aggregate(jnp.zeros(2), "warp-drive", "model")


def test_out_spec_builders():
    assert collectives.out_spec("allreduce", "model", ("data", None, None)) \
        == P("data", None, None)
    assert collectives.out_spec("scatter", "model", ("data", None, None)) \
        == P("data", None, "model")
    assert collectives.out_spec("scatter", "model", ("data", None, None),
                                scatter_dim=1) == P("data", "model", None)
    assert collectives.out_spec("layers", "model", ("data", None, None)) \
        == P("model", "data", None, None)
    with pytest.raises(ValueError):
        collectives.out_spec("scatter", "model", ("data",), scatter_dim=0)


def test_register_custom_mode_dispatches():
    calls = []
    mode = collectives.AggregationMode(
        name="_test_ring",
        combine=lambda partial, axis, sd: calls.append(axis) or partial,
        out_spec=lambda axis, base, sd: P(*base),
        link_byte_factor=lambda p: 42.0,
        description="test-only")
    collectives.register_mode(mode)
    try:
        with pytest.raises(ValueError):
            collectives.register_mode(mode)  # dup without overwrite
        assert "_test_ring" in collectives.available_modes()
        out = collectives.aggregate(jnp.ones(3), "_test_ring", "model")
        assert calls == ["model"] and out.shape == (3,)
        assert collectives.collective_bytes_per_device(
            10, 8, "_test_ring", 1) == 420.0
    finally:
        collectives.unregister_mode("_test_ring")
    assert "_test_ring" not in collectives.available_modes()


def test_aggregate_modes_single_device_parity():
    """All three modes reduce to the plain matmul on a 1-device mesh (the
    multi-device equivalence lives in test_distributed.py)."""
    from repro.core.lbp_matmul import lbp_matmul, lbp_matmul_reference
    mesh = compat.make_mesh((1,), ("model",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(8, 6)), jnp.float32)
    ref = np.asarray(lbp_matmul_reference(x, w))
    for mode in ("layers", "allreduce", "scatter"):
        out = lbp_matmul(x, w, mesh, axis="model", mode=mode)
        got = np.asarray(out.sum(0) if mode == "layers" else out)
        np.testing.assert_allclose(got, ref, atol=1e-5)
