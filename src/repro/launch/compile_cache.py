"""Where JAX keeps its persistent compilation cache.

Launchers and ``chip_smoke.py`` call ``use_compile_cache()`` before their
first compile; importing this module sets nothing, so tests keep JAX's
defaults.
"""

from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at one directory; return it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads that variable
    itself and nothing is set here.  Otherwise the cache lives at the fixed
    path ``<repo>/.jax_cache`` — never a temporary or per-run name, since a
    later run only finds what was cached at the same path.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
