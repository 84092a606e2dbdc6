"""Compile a cell's serving programs for a described TPU v5e; print memory.

    JAX_PLATFORMS=cpu python bench/memcheck.py --config granite_8b \
        --traffic chat-poisson

No chip is needed: the TPU compiler runs here for a chip that is described,
not attached.  It compiles the grouped prefill at the widest group and the
longest prompt, one paged decode step, and the decode scan at the largest
k the traffic can ask for, and prints ``memory_analysis()`` of each with
the bytes the process would hold beside it.  A program that does not fit
is refused by the compiler.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GIB = 1 << 30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import cells, family
    from repro.models import transformer as T
    from repro.serve.engine import PagedTransformerModel
    from repro.serve.step import make_paged_decode_scan
    from repro.sharding.rules import Rules

    jax.config.update("jax_enable_compilation_cache", False)
    spec = json.loads((cells.REPO / "bench/configs" / f"{args.config}.json")
                      .read_text())
    traffic = json.loads((cells.TRAFFIC_DIR / f"{args.traffic}.json")
                         .read_text())
    cell = cells.Cell(f"{args.config}.{args.traffic}", 1, spec, traffic,
                      [], [])
    ec = cell.engine_config()
    fam = family.load(spec)
    cfg = fam.model_config(spec)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    params = placed(jax.eval_shape(fam.init_weights(cfg),
                                   jax.ShapeDtypeStruct((2,), jnp.uint32)))
    n_pages, pps = ec.pool_pages, ec.pages_per_slot
    pool = placed(jax.eval_shape(
        lambda: T.init_cache(cfg, n_pages + 1, ec.page_size)))
    nbytes = lambda t: sum(a.size * a.dtype.itemsize
                           for a in jax.tree_util.tree_leaves(t))
    w_b, p_b = nbytes(params), nbytes(pool)
    print(f"{cell.name}: {cfg.n_layers} layers, {ec.n_slots} slots x "
          f"{ec.pool_len} positions, {n_pages} pages of {ec.page_size}; "
          f"weights {w_b / GIB:.3f} GiB, page pool {p_b / GIB:.3f} GiB")

    adapter = PagedTransformerModel(None, cfg, Rules.null())
    S, B = ec.max_prompt_len, ec.max_prefill_per_step
    k_max = 1 << ((ec.max_new_cap - 1).bit_length() - 1)
    progs = {
        f"prefill B={B} S={S}": (
            adapter._paged_prefill,
            (pps * ec.page_size, params, i32(B, S), i32(B), i32(B),
             i32(B, pps), pool, i32(ec.n_slots), i32(ec.n_slots))),
        "decode k=1": (
            adapter._paged_decode1,
            (params, i32(ec.n_slots), i32(ec.n_slots), pool,
             i32(ec.n_slots, pps), i32(ec.n_slots, pps))),
        f"decode scan k={k_max}": (
            jax.jit(make_paged_decode_scan(cfg, Rules.null(), k_max)),
            (params, i32(ec.n_slots), i32(ec.n_slots), pool,
             i32(ec.n_slots, pps), i32(ec.n_slots, pps))),
    }
    for name, (fn, a) in progs.items():
        m = fn.lower(*a).compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"  {name}: arguments {m.argument_size_in_bytes / GIB:.3f} "
              f"GiB, output {m.output_size_in_bytes / GIB:.3f} GiB, "
              f"temporaries {m.temp_size_in_bytes / GIB:.3f} GiB, aliased "
              f"{m.alias_size_in_bytes / GIB:.3f} GiB -> {total / GIB:.3f} "
              f"GiB of 15.75", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
