"""Device meshes.

``make_production_mesh`` is the deployment target: 16x16 (one v5e pod,
256 chips) or 2x16x16 (two pods, 512 chips).  It is a FUNCTION, not a
module-level constant — importing this module never touches jax device
state (device count is locked at first jax init, and smoke tests must see
the real single-CPU device, not the dry-run's 512 placeholders).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Mesh over whatever devices exist (CPU smoke / small hosts)."""
    n = jax.device_count()
    assert n % model_axis == 0, (n, model_axis)
    return _mesh((n // model_axis, model_axis), ("data", "model"))


def mesh_chip_count(mesh) -> int:
    return mesh.devices.size


# TPU v5e hardware constants for the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BANDWIDTH = 819e9           # B/s
ICI_BANDWIDTH = 50e9            # B/s per link
