"""Mesh construction with the axis types this repo's sharding code expects.

``jax.make_mesh`` defaults to ``Explicit`` axes, under which eager code
that mixes a sharded and a replicated operand raises. The trainers and
tests build their meshes here, with ``Auto`` axes unless asked otherwise.
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh"]


def make_mesh(axis_shapes, axis_names, *, devices=None,
              explicit: bool = False):
    """``jax.make_mesh`` with every axis ``Auto`` (or ``Explicit``)."""
    kind = jax.sharding.AxisType.Explicit if explicit \
        else jax.sharding.AxisType.Auto
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(kind,) * len(axis_names), **kw)
