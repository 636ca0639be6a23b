"""Production meshes.

Single pod: 16x16 = 256 chips (TPU v5e pod), axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model); the pod axis is
pure data parallelism for training (cross-pod gradient all-reduce over
DCN/ICI) and replica/phase-pool parallelism for serving.

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # The model code places arrays with with_sharding_constraint, which
    # accepts only Auto axes; make_mesh defaults to Explicit ones.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over however many (possibly forced-host) devices exist."""
    return _auto_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]
