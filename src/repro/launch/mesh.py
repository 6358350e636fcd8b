"""Production mesh construction.

Target: TPU v5e pods — 256 chips/pod arranged (data=16, model=16);
multi-pod adds a leading 'pod' axis (2 pods = 512 chips for the dry-run,
the same code scales the pod axis to O(1000)-node fleets: the pod axis
only ever carries data-parallel all-reduces, which scale O(bytes) per
chip regardless of pod count).

Defined as a function so importing this module never touches jax device
state (jax locks the device count on first backend init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: placement comes from the NamedShardings of the params and
    # the ``logical`` constraints, and XLA's partitioner fills in the rest
    # (explicit axes would demand an out_sharding on every gather).
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Single-host mesh (all local devices on the data axis) for examples."""
    n = len(jax.devices())
    return _mesh((n, 1), ("data", "model"))
