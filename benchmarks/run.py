"""Benchmark driver: one section per paper figure + the roofline report.

  PYTHONPATH=src python -m benchmarks.run [--skip-roofline] [--skip-serve]
  PYTHONPATH=src python -m benchmarks.run --smoke [--out-dir DIR]

Prints human-readable sections followed by ``name,value,note`` CSV rows
(the machine-readable summary used by EXPERIMENTS.md).  The trajectory
artifacts — ``BENCH_plan.json`` / ``BENCH_serve.json`` /
``BENCH_overlap.json`` — are written to the REPOSITORY ROOT (same
filenames CI emits), so perf is tracked across PRs.

``--smoke`` is the consolidated CI entry point: it runs ONLY the three
trajectory benchmarks (plan / overlap / serve) and writes their JSON
artifacts into ``--out-dir`` (default: the repo root).  CI points
``--out-dir`` at a scratch directory so ``benchmarks.check_regression``
can diff the fresh artifacts against the committed repo-root copies.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import runpy
import subprocess
import sys
import time
import traceback

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def run_trajectory(out_dir: pathlib.Path, rows, out,
                   skip_serve: bool = False) -> bool:
    """The three trajectory benchmarks -> out_dir/BENCH_*.json.
    Returns False if any section failed."""
    out_dir.mkdir(parents=True, exist_ok=True)
    ok = True

    # planning subsystem: flat star vs two-level hierarchy on the
    # production multi-pod shape
    t0 = time.time()
    from . import plan as plan_bench
    pr = plan_bench.main(["--smoke",
                          "--out", str(out_dir / "BENCH_plan.json")])
    rows.append(("plan.hier_finish_speedup_x", pr["finish_speedup"],
                 "flat star priced on the true shared trunks"))
    rows.append(("plan.hier_dcn_reduction_pct", pr["dcn_reduction"] * 100,
                 "distribution volume on DCN trunks"))
    out(f"[plan benchmarks {time.time()-t0:.1f}s]")

    # overlapped layer-streaming plane: needs 8 host devices, so it runs
    # as a subprocess (this process keeps the real device topology)
    t0 = time.time()
    from ._util import host_device_env
    env = host_device_env(8)
    env.setdefault("PYTHONPATH", str(REPO_ROOT / "src"))
    overlap_out = out_dir / "BENCH_overlap.json"
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.overlap", "--smoke",
         "--out", str(overlap_out)],
        env=env, cwd=str(REPO_ROOT), capture_output=True, text=True)
    if r.returncode == 0:
        ov = json.loads(overlap_out.read_text())
        rows.append(("overlap.predicted_speedup_x",
                     ov["prediction"]["predicted_overlap_speedup"],
                     "serial vs max(comm, compute) on 2x16x16"))
        rows.append(("overlap.roofline_speedup_x",
                     ov["prediction"]["roofline_split"]["overlap_speedup"],
                     "serial vs overlapped collective bound"))
    else:
        ok = False
        out(f"[overlap benchmark FAILED]\n{r.stdout}\n{r.stderr}")
    out(f"[overlap benchmarks {time.time()-t0:.1f}s]")

    # serving engine vs fixed batches + paged-vs-slot comparison
    if not skip_serve:
        t0 = time.time()
        from . import serve as serve_bench
        sr = serve_bench.main(["--smoke",
                               "--out", str(out_dir / "BENCH_serve.json")])
        rows.append(("serve.engine_speedup_x", sr["speedup"],
                     "continuous batching vs fixed batches (smoke)"))
        rows.append(("serve.paged_vs_slot_x",
                     sr["paged_vs_slot"]["tokens_per_sec_ratio"],
                     "paged KV plane vs slot plane tok/s"))
        rows.append(("serve.fleet_token_identical",
                     float(sr["fleet"]["token_identical"]),
                     "3-replica fleet == single engine under kill/join"))
        rows.append(("serve.fleet_requeued", float(sr["fleet"]["requeued"]),
                     "requests requeued by the mid-decode kill"))
        rows.append(("serve.sharing_capacity_ratio_x",
                     sr["prefix_sharing"]["capacity_ratio"],
                     "peak pages private reservation vs prefix sharing"))
        rows.append(("serve.sharing_token_identical",
                     float(sr["prefix_sharing"]["token_identical_vs_private"]
                           and sr["prefix_sharing"]
                           ["token_identical_vs_oracle"]),
                     "sharing == private plane == greedy oracle"))
        out(f"[serve benchmarks {time.time()-t0:.1f}s]")

        # the shared-prefix example doubles as an end-to-end smoke: it
        # asserts oracle identity + the capacity win on its own workload.
        # It runs in this process, which already holds the device.
        t0 = time.time()
        try:
            runpy.run_path(str(REPO_ROOT / "examples" / "prefix_sharing.py"),
                           run_name="prefix_sharing")["main"]([])
        except AssertionError:
            ok = False
            out(f"[prefix-sharing example FAILED]\n{traceback.format_exc()}")
        out(f"[prefix-sharing example {time.time()-t0:.1f}s]")
    return ok


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--skip-serve", action="store_true",
                    help="skip the (slow) serving-engine smoke")
    ap.add_argument("--smoke", action="store_true",
                    help="trajectory benchmarks only (the consolidated "
                         "CI step); honors --out-dir")
    ap.add_argument("--out-dir", default=str(REPO_ROOT),
                    help="where BENCH_*.json artifacts land")
    args = ap.parse_args()

    rows = []

    def out(msg=""):
        print(msg, flush=True)

    out_dir = pathlib.Path(args.out_dir)

    if args.smoke:
        ok = run_trajectory(out_dir, rows, out,
                            skip_serve=args.skip_serve)
        out("\n=== name,value,note CSV ===")
        out("name,value,note")
        for name, val, note in rows:
            out(f"{name},{val:.4f},{note}")
        if not ok:
            sys.exit(1)
        return

    t0 = time.time()
    from . import star
    rows += [("bench", "fig6", "star 16-child")] and star.report(out)
    out(f"[star benchmarks {time.time()-t0:.1f}s]")

    t0 = time.time()
    from . import mesh
    rows += mesh.report(out)
    out(f"[mesh benchmarks {time.time()-t0:.1f}s]")

    ok = run_trajectory(out_dir, rows, out, skip_serve=args.skip_serve)

    # scheduler-plane wall time (the runtime re-solves these on rebalance)
    import numpy as _np
    from repro.core.network import random_mesh, random_star
    from repro.core.star import solve as star_solve
    from repro.core.integer_adjust import solve_integer
    from repro.core.heuristic import mft_lbp_heuristic
    net = random_star(16, seed=0)
    m5 = random_mesh(5, 5, seed=0)
    for name, fn, reps in [
        ("star_pccs_solve", lambda: star_solve(net, 1000, "PCCS"), 200),
        ("star_integer_adjust", lambda: solve_integer(net, 1000, "PCCS"), 50),
        ("mesh_heuristic_5x5", lambda: mft_lbp_heuristic(m5, 1000), 5),
    ]:
        t = time.time()
        for _ in range(reps):
            fn()
        us = (time.time() - t) / reps * 1e6
        rows.append((f"sched.{name}_us", us, "solver wall time per call"))

    if not args.skip_roofline:
        from . import roofline_report
        rows += roofline_report.report(out)

    out("\n=== name,value,note CSV ===")
    out("name,value,note")
    for name, val, note in rows:
        out(f"{name},{val:.4f},{note}")
    if not ok:   # a trajectory section failed: exit red, not green
        sys.exit(1)


if __name__ == "__main__":
    main()
