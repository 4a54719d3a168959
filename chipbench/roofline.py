"""Chip peaks and the least work of a round: the yardstick of the roofline.

PEAKS is keyed by ``device.device_kind`` as JAX reports it. A device that
is not in the table is an error, never a default.
"""
from __future__ import annotations

import math

import numpy as np

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {', '.join(PEAKS)}")
    return PEAKS[device_kind]


def n_params(config: dict) -> int:
    return sum(math.prod(shape) for _, shape in config["leaves"])


def least_bytes_per_round(config: dict, k: int) -> int:
    """The HBM bytes any implementation of one round has to move: each of
    the K updates read once, the global model read once where the
    algorithm applies a step to it (FedSGD), the new model written once.
    Accumulators, copies and launches are the implementation's, not the
    work's, and are not counted."""
    item = np.dtype(config["dtype"]).itemsize
    reads = k + (1 if config["algorithm"] == "fedsgd" else 0)
    return (reads + 1) * n_params(config) * item
