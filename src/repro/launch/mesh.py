"""Production meshes (TPU v5e target).

Functions, not module-level constants, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    return _make_mesh(tuple(shape), tuple(axes))


def make_host_mesh(devices=None):
    """``devices`` (default: every local device) as a ("data",) mesh."""
    devices = jax.devices() if devices is None else list(devices)
    return jax.sharding.Mesh(devices, ("data",),
                             axis_types=(AxisType.Auto,))


def stage_device_sets(stage_plan, devices=None) -> list:
    """Per-stage device slices for a ``repro.exec.stages.StagePlan`` on
    the local host (proportional to the topology's group sizes).
    Raises ``repro.exec.stages.PipelineInfeasible`` when the host has
    fewer devices than stages — callers fall back to single-mesh rules."""
    return stage_plan.assign_local_devices(
        jax.devices() if devices is None else devices)


# TPU v5e hardware constants (per chip) used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s per link
    "hbm_bytes": 16e9,
}
