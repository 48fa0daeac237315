"""Activation sharding hints (the MaxText "logical constraint" pattern).

GSPMD's propagation cannot by itself keep attention heads / MoE experts /
mamba channels sharded through reshapes and gathers, so the model code marks
the key activations with ``with_sharding_constraint``. Hints are no-ops when
no mesh is active (CPU smoke tests) or when a named logical axis is absent
from the ambient mesh.

Logical axes:
- "dp":    the batch axes — ("pod", "data") when present
- "model": tensor-parallel axis

Uneven dimensions (e.g. phi3's 40 heads on a 16-way model axis) are allowed —
GSPMD pads; the waste shows up in the roofline and is called out there.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, PartitionSpec as P


def current_mesh():
    """The ambient mesh set by ``jax.set_mesh(mesh)`` (as an abstract mesh:
    axis names and sizes, usable inside ``jit``), or None outside one and
    inside a ``shard_map`` region, whose axes are all ``Manual`` — there the
    region's own specs place every array and a constraint has no axis to
    name."""
    m = jax.sharding.get_abstract_mesh()
    if m.empty or AxisType.Auto not in m.axis_types:
        return None
    return m


def _resolve(mesh, axis):
    if axis is None:
        return None
    if axis == "dp":
        axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]
    if axis in mesh.axis_names:
        return axis
    return None


def model_axis_if(dim: int):
    """'model' when the ambient mesh has it AND it divides ``dim`` evenly
    (used where padded/uneven sharding would be wasteful, e.g. kv caches)."""
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    return "model" if dim % mesh.shape["model"] == 0 else None


def hint(x: jax.Array, *axes) -> jax.Array:
    """Constrain ``x`` with the given logical axes (None = unconstrained)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"hint rank mismatch: {axes} vs {x.shape}")
    spec = P(*[_resolve(mesh, a) for a in axes])
    return jax.lax.with_sharding_constraint(x, spec)
