"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/collective artifacts.

This is the proof that the distribution config is coherent without real
hardware: ``.lower().compile()`` must succeed for the 16x16 (256-chip
single-pod) mesh AND the 2x16x16 (512-chip multi-pod) mesh for every cell.
Artifacts (bytes/device, HLO FLOPs, collective bytes) land in
``artifacts/dryrun/<mesh>/<arch>__<shape>.json`` and feed EXPERIMENTS.md
§Dry-run and §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_2_3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--profile train_sp]

Runs on 512 virtual CPU devices, which it asks for itself before jax is
imported: ``JAX_PLATFORMS=cpu`` keeps it off a TPU the host may have.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import pathlib
import traceback

import jax

from ..analysis.hlo_cost import analyze_hlo
from ..obs import clock as obs_clock
from ..compat import cost_analysis as compat_cost_analysis
from ..configs import ARCH_IDS
from ..configs.shapes import cells_for
from .compile_cache import use_compile_cache
from .input_specs import make_plan
from .mesh import make_production_mesh

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             profile: str | None = None, grad_accum: int | None = None,
             save: bool = True, tag: str = "", tuning: dict | None = None) -> dict:
    if tuning:
        from ..models.tuning import set_tuning
        set_tuning(**tuning)
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = make_plan(arch, shape, mesh, profile_override=profile,
                     grad_accum=grad_accum)

    t0 = obs_clock.wall_time()
    with mesh:
        jitted = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                         out_shardings=plan.out_shardings,
                         donate_argnums=plan.donate)
        lowered = jitted.lower(*plan.args)
        t_lower = obs_clock.wall_time() - t0
        compiled = lowered.compile()
        t_compile = obs_clock.wall_time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compat_cost_analysis(compiled)
    parsed = analyze_hlo(compiled.as_text())
    coll = parsed["collectives"]

    n_dev = mesh.devices.size
    result = {
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "profile": profile or "default",
        "grad_accum": grad_accum,
        "tag": tag,
        "n_devices": int(n_dev),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "bytes_per_device": {
            "argument": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak": int(getattr(mem, "peak_memory_in_bytes", 0) or
                        (getattr(mem, "argument_size_in_bytes", 0) +
                         getattr(mem, "temp_size_in_bytes", 0))),
        },
        # loop-aware (trip-count-multiplied) instruction-level parse:
        "hlo_flops": parsed["flops"],
        "hlo_bytes": parsed["hbm_bytes"],
        # raw XLA aggregates (NOT loop-multiplied; kept for cross-checking):
        "xla_flops_raw": float(cost.get("flops", 0.0)) if cost else 0.0,
        "xla_bytes_raw": float(cost.get("bytes accessed", 0.0)) if cost else 0.0,
        "collectives": coll,
    }
    if save:
        sub = ARTIFACTS / result["mesh"]
        sub.mkdir(parents=True, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        (sub / f"{arch}__{shape}{suffix}.json").write_text(
            json.dumps(result, indent=1))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--profile", default=None)
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--tuning", default="",
                    help="comma list k=true/false for models.tuning flags")
    args = ap.parse_args()
    use_compile_cache()

    tuning = {}
    for kv in filter(None, args.tuning.split(",")):
        k, v = kv.split("=")
        tuning[k] = v.lower() in ("1", "true", "yes", "on")

    if args.all:
        cells = [(a, n) for a in ARCH_IDS for (n, _) in cells_for(a)]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            label = f"{arch} x {shape} [{'2x16x16' if mp else '16x16'}]"
            try:
                r = run_cell(arch, shape, multi_pod=mp, profile=args.profile,
                             grad_accum=args.grad_accum, tag=args.tag,
                             tuning=tuning)
                peak = r["bytes_per_device"]["peak"] / 2**30
                print(f"OK   {label:55s} peak={peak:6.2f} GiB/dev "
                      f"flops={r['hlo_flops']:.3e} "
                      f"coll={r['collectives']['total_bytes']/2**30:.2f} GiB "
                      f"compile={r['compile_s']:.0f}s", flush=True)
            except Exception as e:
                failures.append((label, repr(e)))
                traceback.print_exc()
                print(f"FAIL {label}: {e}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for l, e in failures:
            print(" ", l, e[:200])
        raise SystemExit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
