"""Mesh factory.

A function (not a module-level constant) so importing this module never
touches jax device state -- the dry-run must set XLA_FLAGS first.

The mesh is built from the devices present: ``("data", "model")`` with
the model axis the largest divisor of the device count not above its
square root, so 256 devices give the single-pod (16, 16) and 4 devices
give (2, 2).  Multi-pod adds an outer ``"pod"`` axis of 2 (512 devices:
(2, 16, 16)); it is an outer DP dimension whose collectives ride DCN,
everything else stays on ICI.

Every axis is ``Auto``: the model code places activations with
``with_sharding_constraint``, which refuses ``Explicit`` axes.
"""

from __future__ import annotations

import math
import os

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "mesh_shape"]


def mesh_shape(n_devices: int, *, multi_pod: bool = False) -> tuple[int, ...]:
    """(data, model) -- or (pod, data, model) -- for ``n_devices``."""
    pods = 2 if multi_pod else 1
    if n_devices % pods:
        raise ValueError(f"multi-pod mesh needs an even device count, "
                         f"have {n_devices}")
    n = n_devices // pods
    model = max(d for d in range(1, math.isqrt(n) + 1) if n % d == 0)
    return ((pods,) if multi_pod else ()) + (n // model, model)


def make_production_mesh(*, multi_pod: bool = False):
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # test hook: REPRO_MESH_OVERRIDE="4x2" (single pod) / "2x2x2" (multi-pod)
    # lets the mini dry-run tests pick a mesh shape on the handful of host
    # devices available under pytest.
    ov = os.environ.get("REPRO_MESH_OVERRIDE")
    if ov:
        dims = tuple(int(d) for d in ov.split("x"))
        if len(dims) == len(axes):
            return make_mesh(dims, axes)
    return make_mesh(mesh_shape(jax.device_count(), multi_pod=multi_pod),
                     axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Mesh of ``Auto`` axes (tests use e.g. (2, 4) on 8 host devices)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
