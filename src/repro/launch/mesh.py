"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module touches no jax device state — required because the dry-run must set
XLA_FLAGS before the first jax initialisation.

Target hardware: TPU v5e pods, 256 chips/pod, 16×16 ICI torus.
  single-pod:  (16, 16)       axes ("data", "model")
  multi-pod:   (2, 16, 16)    axes ("pod", "data", "model") — "pod" is pure
               data parallel over the inter-pod (DCN/DCI) links; gradient
               reduction over it optionally runs int8 error-feedback
               compression (optim/compression.py).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "mesh_axis_sizes"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Whatever devices exist, as a 1×N ("data","model") mesh (tests/CPU)."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"), (AxisType.Auto,) * 2)


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
