"""Production mesh construction (TPU v5e pod targets).

Functions, not module-level constants: importing this module never touches
jax device state. The dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax.
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """TPU v5e per-chip constants used by the roofline analysis."""

    peak_flops_bf16: float = 197e12  # FLOP/s
    hbm_bw: float = 819e9  # B/s
    ici_link_bw: float = 50e9  # B/s per link
    hbm_bytes: float = 16e9  # capacity
    # cross-pod (DCN) bandwidth per chip, used for the multi-pod collective term
    dcn_bw: float = 6.25e9  # B/s (~50 Gb/s per host NIC share)


V5E = HardwareSpec()


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the sharding rules place arrays
    with ``with_sharding_constraint``, which refuses ``Explicit`` axes (the
    default of ``jax.make_mesh``)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2):
    """Small mesh over the local devices (forced host devices in tests)."""
    return _auto_mesh((data, model), ("data", "model"))


def n_chips(mesh) -> int:
    return int(mesh.devices.size)
