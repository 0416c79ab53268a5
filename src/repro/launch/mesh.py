"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  512 chips as (pod=2, data=16, model=16) — the pod axis is the
outer data-parallel / pipeline axis (slowest links).

Defined as functions (never module-level constants) so importing this module
never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax use.
"""
from __future__ import annotations

import jax


def auto_axes(n: int) -> tuple:
    """``axis_types`` for an ``n``-axis mesh whose shardings the compiler
    propagates (``jax.make_mesh`` would otherwise make every axis explicit)."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=auto_axes(len(axes)))


def make_lanes_mesh(num_lanes: int):
    """1-D ``lanes`` mesh over the first ``num_lanes`` local devices — the
    serving pipeline's parallel-lane axis (paper §2.2: parallel extractor
    lanes over the multi-bank memory fabric).  Unlike the production meshes
    this may use a subset of the devices: lanes are a serving concept, not a
    training topology."""
    import numpy as np

    devices = jax.devices()
    if num_lanes > len(devices):
        raise ValueError(f"need {num_lanes} devices for a lanes mesh, "
                         f"have {len(devices)}")
    return jax.sharding.Mesh(np.asarray(devices[:num_lanes]), ("lanes",))


def make_host_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small mesh for CPU integration tests (requires the host-device flag)."""
    if pod:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"),
                             axis_types=auto_axes(3))
    return jax.make_mesh((data, model), ("data", "model"), axis_types=auto_axes(2))


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
