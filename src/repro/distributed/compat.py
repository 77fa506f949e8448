"""Mesh and cost-analysis helpers for the distribution layer.

Every mesh in the repo is built through `make_mesh` so all axes are
explicitly `AxisType.Auto` (sharding propagated by the compiler), and
`cost_analysis_dict` gives callers a plain dict even when XLA reports
nothing.
"""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """Build a mesh with Auto axis types."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict ({} when XLA provides nothing)."""
    return compiled.cost_analysis() or {}
