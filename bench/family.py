"""Find a model family's code by the configuration file's ``family``.

A family is one file, ``bench/families/<family>.py``, that provides:

- ``model_config(spec)``: the program's ``ModelConfig`` for one chip's
  share of the configuration file ``spec``;
- ``init_weights(cfg)``: seed key -> the program's parameter tree, made on
  the device in one jitted call (draws from ``bench.common``);
- ``reference_logits(cfg, seed, seqs, rows, length, quant=None)``: the
  plain float32 forward pass at ``HIGHEST`` precision over ``seqs`` packed
  into one row of ``length`` (``common.pack``), its logits at ``rows``;
  ``quant`` one precision step lower (the controls of ``common``);
- ``weight_bytes(cfg)``, ``decode_step_bytes(cfg, call)``,
  ``decode_flops(cfg, call)``, ``prefill_flops(cfg, length)``: operations
  and bytes from shapes, where ``call`` is one record of the harness's
  ``Calls.decode`` (``harness.Decode``: its ``k`` steps, its live rows'
  depths and, in a traced run, the numeric arguments of the program's
  spans around the call in ``program``);
- ``SCOPES``: the ``jax.named_scope`` names that the family's programs
  carry, by which ``bench.program_trace`` splits their device time.
"""

from __future__ import annotations

import importlib.util
import pathlib

DIR = pathlib.Path(__file__).resolve().parent / "families"
_LOADED = {}


def load(spec: dict):
    """The module of ``spec["family"]``, loaded once per file."""
    path = DIR / f"{spec['family']}.py"
    if path not in _LOADED:
        if not path.is_file():
            raise FileNotFoundError(
                f"no model family {spec['family']!r}: {path} is missing")
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_family_{spec['family']}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]
