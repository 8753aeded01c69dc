"""Thin helpers over jax APIs whose defaults do not fit this codebase.

* ``current_mesh()`` — the ambient mesh (``jax.set_mesh``) used for
  sharding hints, or ``None`` when no mesh with named axes is active; call
  sites only read ``.axis_names`` and the ``.shape`` mapping.
* ``make_mesh(shape, axes)`` — ``jax.make_mesh`` with Auto axis types
  (jax defaults to Explicit, which every mesh here predates).
* ``pvary(x, axis)`` — an idempotent ``jax.lax.pcast(..., to="varying")``:
  the cast refuses a value that already varies over the axis.
"""

from __future__ import annotations

from typing import Optional

import jax


def current_mesh() -> Optional[object]:
    """The ambient mesh (``jax.set_mesh`` context), or ``None`` when no
    mesh with named axes is active."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def pvary(x, axis: str):
    """Promote ``x`` to axis-varying unless it already is."""
    if axis in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, axis, to="varying")
