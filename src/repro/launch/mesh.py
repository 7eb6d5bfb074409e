"""Production meshes. Functions (not module constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax call.

Target hardware: TPU v5e pods; 256 chips/pod in a (16, 16) = (data, model)
layout; the multi-pod config stacks a leading "pod" axis (2, 16, 16).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1) -> Mesh:
    """Whatever devices exist locally, data-major (CPU tests/examples)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def make_machine_mesh(n_devices: int) -> Mesh:
    """1-D ``("machines",)`` mesh over ``n_devices`` devices for the SPMD
    protocol. The axis is ``Auto``: jax.make_mesh defaults to ``Explicit``
    axes, under which slicing a machine-sharded array (Algorithm 1 takes
    machine 0 as the center) is a sharding type error."""
    return jax.make_mesh((n_devices,), ("machines",),
                         axis_types=(AxisType.Auto,))


# roofline hardware constants (TPU v5e, per chip)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # bytes/s
ICI_BW = 50e9                  # bytes/s per link
