"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any device query).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_axes(n: int) -> tuple:
    """``Auto`` axis types: sharding constraints stay hints to the
    partitioner (``jax.make_mesh`` defaults to ``Explicit`` axes, under
    which ``dist.ctx.constrain`` would become a type assertion)."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = ('data', 'model') -- 256 chips.
    Multi-pod:  (2, 16, 16) = ('pod', 'data', 'model') -- 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=auto_axes(len(axes)))


def make_local_mesh(model_parallel: int = 1):
    """Whatever this host has (tests / examples)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=auto_axes(2))


def make_wide_mesh(n: int | None = None):
    """1-D mesh over the ``wide`` axis for sharded wide aggregation
    (core.aggregate): each slab segment's rows split across this axis and
    partial bitset words / bit-sliced counters all-reduce over it.

    ``n`` defaults to every local device; a 1-device mesh makes the
    aggregates fall back to the single-dispatch path, so this is always
    safe to install via ``aggregate.set_default_mesh``."""
    from jax.experimental import mesh_utils
    devs = jax.devices()
    n = len(devs) if n is None else min(n, len(devs))
    return jax.sharding.Mesh(
        mesh_utils.create_device_mesh((n,), devices=devs[:n]), ("wide",),
        axis_types=auto_axes(1))


# Hardware constants for the roofline analysis (TPU v5e-class chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
