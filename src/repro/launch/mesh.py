"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, logical axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, leading pure-DP "pod" axis (gradient
all-reduce over DCI/ICI between pods; the e5m2 compressed reduction in
``optim.grad_compress`` targets exactly this axis).

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """The data-parallel axis names of a mesh (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n
