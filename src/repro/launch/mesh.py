"""Production mesh construction (TPU v5e pods; host-device stand-ins on CPU).

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the code places arrays with
    NamedSharding/GSPMD and explicit shard_map, not sharding-in-types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Single-process mesh over whatever devices exist (smoke/e2e runs)."""
    n = len(jax.devices())
    return _make_mesh((n, 1), ("data", "model"))


def make_data_mesh(num_shards: int = 0):
    """1-D ``("data",)`` mesh for data-parallel training.

    ``num_shards=0`` takes every local device (the "no code change across
    hardware" default: the same config scales to whatever is attached);
    an explicit count must not exceed the devices that exist.  On CPU,
    fake devices come from ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    set *before* the first jax import.
    """
    avail = len(jax.devices())
    n = avail if num_shards in (0, None) else int(num_shards)
    if n > avail:
        raise ValueError(
            f"data_parallel={num_shards} but only {avail} device(s) exist; "
            f"on CPU export XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{num_shards} before starting python")
    return _make_mesh((n,), ("data",))


def dp_axes(mesh) -> tuple:
    """Mesh axes that carry the batch (data-parallel) dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# Hardware constants for the roofline analysis (TPU v5e).
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
