"""Pallas TPU kernel: N-way weighted fusion of flattened model updates.

The paper models aggregation cost as (N_parties - 1) sequential pairwise
fusions (t_pair each). On TPU the operation is bandwidth-bound, so we fuse
all K updates resident in one VMEM tile in a single HBM sweep:

  out[n] = sum_k w[k] * updates[k, n]

Tiling: grid (N/BN, K/KB), the reduction axis last. Each step streams a
(KB, BN) tile of updates into VMEM and accumulates its weighted sum into
the fp32 output tile, which stays resident in VMEM across the K steps of
one N block and is written back once (a TPU pipeline never reads an output
block back, so a block revisited after another one would lose its sum).
The K weights live whole in SMEM; the kernel reads them as scalars, eight
rows (one fp32 sublane tile) at a time, and reduces over K with a
broadcast-multiply and a sublane sum.

Block shape: BN is a multiple of 1024 = 8*128 (fp32 VMEM tiles are (8,128));
a (8, 2048) tile keeps VMEM pressure at KB*BN*4B = 64 KiB per input tile.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BN = 2048
DEFAULT_KB = 8
#: rows per weight column: one fp32 (8, 128) sublane tile
_ROWS = 8


def _kernel(w_ref, u_ref, o_ref, *, k: int):
    i = pl.program_id(1)
    kb, bn = u_ref.shape
    rows = math.gcd(kb, _ROWS)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    sub = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    acc = jnp.zeros((rows, bn), jnp.float32)
    for g in range(kb // rows):
        base = i * kb + g * rows
        col = jnp.zeros((rows, 1), jnp.float32)
        for r in range(rows):
            col = jnp.where(sub == r, w_ref[base + r], col)
        u = u_ref[g * rows:(g + 1) * rows, :].astype(jnp.float32)
        if k % kb:  # the last K block runs past the updates: mask its rows
            u = jnp.where(base + sub < k, u, 0.0)
        acc = acc + u * col
    o_ref[...] += jnp.sum(acc, axis=0)


@functools.partial(jax.jit, static_argnames=("bn", "kb", "interpret"))
def weighted_sum(
    x: jax.Array,  # (K, N) float or int8
    w: jax.Array,  # (K,)
    *,
    bn: int,
    kb: int,
    interpret: bool,
) -> jax.Array:
    """sum_k w[k] * x[k, :] in fp32, shape (N,).

    The grid covers (K, N) with ragged last blocks, so the updates are
    never padded or copied: the kernel masks the rows past K, and writes
    past N are dropped."""
    k, n = x.shape
    # a block no larger than the array: XLA lays out a short vector with a
    # smaller tile than a full block's, which Mosaic would refuse
    bn, kb = min(bn, n), min(kb, k)
    gk = pl.cdiv(k, kb)
    w = jnp.pad(w.astype(jnp.float32), (0, gk * kb - k))
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(pl.cdiv(n, bn), gk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((kb, bn), lambda j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda j, i: (j,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret,
    )(w, x)


def fused_agg(
    updates: jax.Array,  # (K, N) any float dtype
    weights: jax.Array,  # (K,)
    *,
    bn: int = DEFAULT_BN,
    kb: int = DEFAULT_KB,
    interpret: bool,
) -> jax.Array:
    """Weighted sum of K updates, returned in the updates' dtype."""
    return weighted_sum(updates, weights, bn=bn, kb=kb,
                        interpret=interpret).astype(updates.dtype)
