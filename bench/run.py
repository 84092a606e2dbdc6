"""Run one benchmark cell once, on the chip this process is started on.

    python3 bench/run.py --workload granite_8b.chat-poisson --seed 7 \
        --seconds 30 --trace 0

The cell is found by name in ``BENCHMARK.json``.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, the programs built inside the window (there should be none),
and last ``checks``: each number the correctness check compared, beside
its limit.  The same numbers are the last lines of standard error.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.  JAX's persistent compilation cache lives where
the program puts it, ``$JAX_COMPILATION_CACHE_DIR`` or else
``<checkout>/.jax_cache``, so only the first run of a cell in a checkout
compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench import cells
    cell = cells.load_cell(args.workload, ROOT)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from bench import harness
    harness.use_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    dev = result["device"]
    print(f"device: {dev['platform']} {dev['kind']} x{dev['count']}; "
          f"programs built inside the window: "
          f"{result['compiles_in_window']} {result['compiled_in_window']}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
