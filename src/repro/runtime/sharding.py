"""Mesh-axis helpers shared by the distributed LDA backend and serving."""

from __future__ import annotations

from typing import Sequence

from jax.sharding import Mesh

__all__ = ["mesh_axis_size", "batch_axes"]


def mesh_axis_size(mesh: Mesh, axes: str | Sequence[str] | None) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """The data-parallel axes: ('pod','data') on multi-pod, ('data',) else."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
