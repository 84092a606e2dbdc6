"""The paper's technique end-to-end on (simulated) heterogeneous devices.

Eight CPU "devices" with different speeds; the star solver computes the
{k_i} split, the ragged LBP matmul executes it, and the three aggregation
modes (layers / allreduce / scatter) are compared for collective bytes on
the compiled HLO.

    PYTHONPATH=src python examples/heterogeneous_matmul.py
(runs on 8 virtual CPU devices)
"""

import os

# eight virtual CPU devices, asked for before jax is imported;
# JAX_PLATFORMS=cpu keeps the script off a TPU the host may have
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo_cost import analyze_hlo
from repro.compat import make_mesh
from repro.core.lbp_matmul import lbp_matmul, lbp_matmul_heterogeneous, lbp_matmul_reference
from repro.core.partition import LayerAssignment
from repro.runtime.rebalance import plan_rebalance

mesh = make_mesh((8,), ("model",))

# --- straggler-aware split from measured speeds ---------------------------
speeds = [1.0, 1.0, 1.0, 0.5, 1.0, 2.0, 1.0, 1.0]   # device 3 slow, 5 fast
plan = plan_rebalance(K=1024, speeds=speeds, quantum=128)
print("measured speeds :", speeds)
print("k_i split       :", plan.assignment.k, f"(sum={plan.assignment.K})")
print(f"predicted speedup vs even split: {plan.predicted_speedup:.2f}x")

x = jax.random.normal(jax.random.PRNGKey(0), (64, 1024))
w = jax.random.normal(jax.random.PRNGKey(1), (1024, 256))
ref = lbp_matmul_reference(x, w)
out = jax.jit(lambda x, w: lbp_matmul_heterogeneous(
    x, w, plan.assignment, mesh, axis="model"))(x, w)
print("ragged matmul max err:", float(jnp.abs(out - ref).max()))

# --- aggregation modes: paper-faithful vs deferred ------------------------
print("\ncollective link bytes per step (compiled HLO, ring model):")
for mode in ("layers", "allreduce", "scatter"):
    c = jax.jit(lambda x, w: lbp_matmul(
        x, w, mesh, axis="model", mode=mode)).lower(x, w).compile()
    coll = analyze_hlo(c.as_text())["collectives"]
    print(f"  {mode:9s}: {coll['total_link_bytes']/1e3:8.1f} KB  {dict((k, int(v['count'])) for k, v in coll['per_op'].items())}")
print("\nlayers = the paper's distributed storage (no aggregation);")
print("scatter = deferred aggregation (reduce-scatter, half of allreduce).")
