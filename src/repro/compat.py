"""The single import point for JAX's distributed API.

The repo supports the installed jax (0.9.0) only.  Every module takes
``shard_map``, ``make_mesh`` and ``cost_analysis`` from here rather than
from jax, so when jax moves or renames one of them the fix lands in this
file alone (CI greps ``src/`` for direct uses outside it).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax import shard_map  # noqa: F401  (re-exported)

AxisType = jax.sharding.AxisType


def _resolve_axis_types(axis_types, n_axes: int):
    """Normalize "auto" | "explicit" | "manual" | AxisType, or a per-axis
    sequence of them, to a tuple of AxisType."""
    if axis_types is None:
        axis_types = "auto"
    if isinstance(axis_types, str) or not isinstance(axis_types, (tuple, list)):
        axis_types = (axis_types,) * n_axes
    if len(axis_types) != n_axes:
        raise ValueError(f"axis_types {axis_types!r} vs {n_axes} axes")

    def one(t):
        if isinstance(t, str):
            try:
                return getattr(AxisType, t.capitalize())
            except AttributeError:
                raise ValueError(f"unknown axis type {t!r}") from None
        return t

    return tuple(one(t) for t in axis_types)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None, axis_types=None):
    """``jax.make_mesh`` with ``axis_types`` also accepted as strings
    ("auto" / "explicit" / "manual"); auto by default."""
    axis_shapes = tuple(int(s) for s in axis_shapes)
    axis_names = tuple(axis_names)
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=_resolve_axis_types(axis_types,
                                                        len(axis_names)),
                         **kwargs)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict; {} when the backend offers
    no cost model."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    return dict(cost) if cost else {}
