"""Shared benchmark helpers (timing + host-device re-exec)."""

from __future__ import annotations

import os
import subprocess
import sys
import time


def time_best(fn, reps: int) -> float:
    """Best wall time over ``reps`` calls (shared CI hosts swing several-
    fold run to run; best-of is the stable statistic)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def host_device_env(n: int = 8) -> dict:
    """A copy of os.environ held to the CPU (``JAX_PLATFORMS=cpu``: on a
    chip host the child would otherwise take the TPU) with ``n`` forced
    host devices APPENDED to XLA_FLAGS (dump/debug flags are preserved;
    an existing device_count pin is respected)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if "device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n}"
                            ).strip()
    return env


def ensure_host_devices(n: int = 8) -> None:
    """Re-exec the current script on the CPU with ``n`` forced host
    devices unless it already runs so.  Must run before jax is
    imported."""
    if ("device_count" in os.environ.get("XLA_FLAGS", "")
            and os.environ.get("JAX_PLATFORMS") == "cpu"):
        return
    raise SystemExit(subprocess.run([sys.executable] + sys.argv,
                                    env=host_device_env(n)).returncode)
