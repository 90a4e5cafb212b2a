"""Mesh axis conventions.

Production meshes (defined in launch/mesh.py as required):
  single-pod: (16, 16)    axes ("data", "model")
  multi-pod:  (2, 16, 16) axes ("pod", "data", "model")

"pod" is the cross-DCN axis: plain DP (gradient all-reduce over DCN) or the
pipeline axis when ParallelConfig.pipeline_stages > 1.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

DATA_AXIS = "data"
MODEL_AXIS = "model"
POD_AXIS = "pod"


def make_mesh(shape, axes, devices=None):
    """Mesh with Auto (GSPMD-propagated) axes; ``devices`` defaults to all."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def axis_size(mesh, name: str) -> int:
    return mesh.shape.get(name, 1) if mesh is not None else 1


def dp_size(mesh) -> int:
    return axis_size(mesh, DATA_AXIS) * axis_size(mesh, POD_AXIS)


def model_size(mesh) -> int:
    return axis_size(mesh, MODEL_AXIS)
