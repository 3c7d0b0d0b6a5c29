"""Production meshes.

A TPU v5e pod is 16x16 = 256 chips; the multi-pod configuration adds a
leading 'pod' axis (2 pods = 512 chips, data-parallel across pods over
DCI). Functions, not module constants: importing this module must never
touch jax device state (the dry-run sets the host-device-count flag
before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh over host (CPU) devices for tests/examples."""
    if pod:
        shape, axes = (pod, data, model), ("pod", "data", "model")
    else:
        shape, axes = (data, model), ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


HW = {
    "peak_flops_bf16": 197e12,  # per chip
    "hbm_bw": 819e9,  # bytes/s per chip
    "ici_bw": 50e9,  # bytes/s per link (approx, v5e)
    "hbm_bytes": 16e9,
}
