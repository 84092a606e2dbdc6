"""Stream vs blocking distributed matmul: "simultaneous start" on a mesh.

The paper's core scheduling claim is that layer decomposition lets the
distribution of layer j+1 overlap the multiplication of layer j, so the
finish time is max(comm, compute) instead of their sum.  This demo runs
both execution planes on an 8-device mesh:

  blocking   all-gather the FSDP weight -> one big einsum -> one
             psum_scatter of the partial layer;
  streamed   the weight shard rides a ppermute ring (one column block
             matmul'd per hop) and the aggregation is an
             accumulate-and-forward tile ring — zero monolithic
             collectives in the lowered HLO.

    PYTHONPATH=src python examples/overlap_streaming.py
(runs on 8 virtual CPU devices)
"""

import os

# eight virtual CPU devices, asked for before jax is imported;
# JAX_PLATFORMS=cpu keeps the script off a TPU the host may have
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()

import jax
import numpy as np

from repro.analysis.hlo_collectives import collective_summary
from repro.compat import make_mesh
from repro.core import collectives
from repro.core.lbp_matmul import lbp_matmul, lbp_matmul_reference
from repro.models import lbp_linear
from repro.models.tuning import set_tuning
from repro.plan import plan, production_topology
from repro.sharding.rules import Rules

# --- 1. the same LBP matmul under blocking and streamed aggregation -------
mesh = make_mesh((8,), ("model",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 512))
w = jax.random.normal(jax.random.PRNGKey(1), (512, 256))
ref = np.asarray(lbp_matmul_reference(x, w))

print("mode              max|err|   ppermutes  AG/AR/RS   link B/device")
for mode in ("allreduce", "scatter", "stream_gather", "stream_scatter"):
    fn = jax.jit(lambda x, w, m=mode: lbp_matmul(x, w, mesh, axis="model",
                                                 mode=m))
    err = np.abs(np.asarray(fn(x, w)) - ref).max()
    summ = collective_summary(fn.lower(x, w).compile().as_text(), 8)
    per_op = summ["per_op"]
    n_pp = per_op.get("collective-permute", {}).get("count", 0)
    n_blk = sum(per_op.get(op, {}).get("count", 0)
                for op in ("all-gather", "all-reduce", "reduce-scatter"))
    analytic = collectives.collective_bytes_per_device(
        x.shape[0] * x.shape[1] * w.shape[1], 8, mode, itemsize=4)
    print(f"{mode:16s}  {err:8.1e}   {n_pp:9.0f}  {n_blk:8.0f}   "
          f"{analytic:12.0f}")

# --- 2. the full row-parallel layer with the FSDP weight ring -------------
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
rules = Rules(batch=("pod", "data"), seq="model", embed="data", ff="model",
              mesh=mesh3)
h = jax.random.normal(jax.random.PRNGKey(2), (4, 8, 64))
wf = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
outs = {}
for name, streaming in (("blocking", False), ("streamed", True)):
    set_tuning(explicit_lbp_scatter=True, overlap_streaming=streaming)
    fn = jax.jit(lambda h, w: lbp_linear.lbp_row_parallel(h, w, rules))
    outs[name] = np.asarray(fn(h, wf))
    summ = collective_summary(fn.lower(h, wf).compile().as_text(), 8)
    print(f"{name:9s} lbp_row_parallel collectives: "
          f"{ {k: v['count'] for k, v in summ['per_op'].items()} }")
set_tuning(explicit_lbp_scatter=False, overlap_streaming=False)
print("streamed == blocking:",
      np.abs(outs["streamed"] - outs["blocking"]).max() < 1e-4)

# --- 3. what the planner predicts the overlap is worth --------------------
topo = production_topology(multi_pod=True)
serial = plan(topo, 2048, objective="PCCS")
ov = plan(topo, 2048, objective="overlap")
print(f"\nproduction 2x16x16, load 2048:")
print(f"  serial  (PCCS)    finish {serial.finish_time:10.1f}  "
      f"(its overlapped price: {serial.finish_time_overlap:10.1f})")
print(f"  overlap objective finish {ov.finish_time:10.1f}  "
      f"-> {serial.finish_time / ov.finish_time:.2f}x predicted")
