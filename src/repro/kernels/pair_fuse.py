"""Pallas TPU kernel: the paper's pairwise coordinate-wise fusion operator

    M1 (+) M2 = [f(M1[1], M2[1]), ..., f(M1[n], M2[n])]

for pairwise (incremental) aggregation, where updates are fused one pair at
a time as they arrive. f is selected statically: mean, weighted sum, max,
min. Elementwise and bandwidth-bound, over 1-D blocks; operands are upcast
to fp32 in the kernel (a bf16 update); weights sit in SMEM. The served
streaming fold does not call it (``repro.fl.fusion.accumulate`` folds by
one XLA multiply-add per slab); ``autotune`` and ``chip_smoke.py`` time and
check it.

The block size ``bn`` is tunable (multiple of 1024 = 8*128 fp32 lanes);
`repro.kernels.autotune` picks it per model size by minimising modeled HBM
traffic (padding waste vs VMEM pressure). The default matches the
pre-autotune constant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BN = 8 * 1024


def _make_kernel(op: str):
    def kernel(w_ref, a_ref, b_ref, o_ref):
        a = a_ref[...].astype(jnp.float32)
        b = b_ref[...].astype(jnp.float32)
        if op == "mean":
            o = 0.5 * (a + b)
        elif op == "wsum":
            o = w_ref[0] * a + w_ref[1] * b
        elif op == "max":
            o = jnp.maximum(a, b)
        elif op == "min":
            o = jnp.minimum(a, b)
        else:
            raise ValueError(op)
        o_ref[...] = o.astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("op", "bn", "interpret"))
def pair_fuse(
    a: jax.Array,  # (N,)
    b: jax.Array,  # (N,)
    *,
    op: str = "mean",
    wa: float = 0.5,
    wb: float = 0.5,
    bn: int = DEFAULT_BN,
    interpret: bool,
) -> jax.Array:
    (n,) = a.shape
    bn = min(bn, n)  # see weighted_sum: a short vector is one whole block
    w = jnp.asarray([wa, wb], jnp.float32)
    # ragged last block: no padded copies of the operands
    return pl.pallas_call(
        _make_kernel(op),
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bn,), lambda i: (i,)),
            pl.BlockSpec((bn,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), a.dtype),
        interpret=interpret,
        name="pair_fuse",
    )(w, a, b)
