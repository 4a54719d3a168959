"""Pallas TPU kernel: int8 dequantise-and-accumulate fusion (beyond-paper).

Parties may ship int8-quantised updates (per-party scale) to cut t_comm by
4x; the aggregator fuses them without ever materialising the dequantised
fp32 updates in HBM:

  out[n] = sum_k scale[k] * q[k, n]

This is fused_agg's weighted-sum kernel with the scales as weights and an
fp32 result; int8 tiles are (32, 128), so BN stays a multiple of 1024 and
KB a multiple of 32 for alignment.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_agg import weighted_sum

DEFAULT_BN = 4096
DEFAULT_KB = 32


def quant_agg(
    q: jax.Array,  # (K, N) int8
    scales: jax.Array,  # (K,) fp32
    *,
    bn: int = DEFAULT_BN,
    kb: int = DEFAULT_KB,
    interpret: bool,
) -> jax.Array:
    return weighted_sum(q, scales, bn=bn, kb=kb, interpret=interpret)


def quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantisation (party side)."""
    x32 = x.astype(jnp.float32).reshape(-1)
    scale = jnp.max(jnp.abs(x32)) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale
