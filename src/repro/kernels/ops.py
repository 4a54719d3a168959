"""jit'd public wrappers over the Pallas kernels, operating on model-update
PYTREES (the paper's "list of one-dimensional vectors, one per layer").

All entry points accept/return pytrees of arrays; leaves are flattened,
fused leaf-wise by the kernels, and reshaped back. The kernel mode is decided
here, once, from the backend (`interpret_mode`): on the CPU backend the
Pallas kernel bodies run in the interpreter, on a TPU they are compiled.
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fused_agg import fused_agg
from repro.kernels.pair_fuse import pair_fuse
from repro.kernels.quant_agg import quant_agg, quantize

Pytree = Any


def interpret_mode() -> bool:
    """Run the Pallas kernels interpreted only on the CPU backend; on any
    accelerator they compile (there is no fallback to the interpreter)."""
    return jax.default_backend() == "cpu"


def _tile_kwargs(bn: Optional[int], kb: Optional[int] = None) -> dict:
    """Autotuned tile overrides (None -> the kernel's built-in default)."""
    kw = {}
    if bn is not None:
        kw["bn"] = bn
    if kb is not None:
        kw["kb"] = kb
    return kw


def fuse_updates(
    updates: Sequence[Pytree],
    weights: Optional[Sequence[float]] = None,
    *,
    bn: Optional[int] = None,
    kb: Optional[int] = None,
) -> Pytree:
    """Weighted fusion of K model updates (FedAvg-style weighted mean when
    weights sum to 1). Leaf-wise: stacks each leaf across updates and runs
    the fused_agg kernel once per leaf. ``bn``/``kb`` override the tile
    shape (see `repro.kernels.autotune.autotune` for the tuned choice)."""
    k = len(updates)
    assert k >= 1
    if weights is None:
        weights = [1.0 / k] * k
    w = jnp.asarray(weights, jnp.float32)
    treedef = jax.tree.structure(updates[0])
    leaves = [jax.tree.leaves(u) for u in updates]
    fused = []
    for i in range(len(leaves[0])):
        stack = jnp.stack([l[i].reshape(-1) for l in leaves])  # (K, N)
        out = fused_agg(stack, w, interpret=interpret_mode(),
                        **_tile_kwargs(bn, kb))
        fused.append(out.reshape(leaves[0][i].shape).astype(leaves[0][i].dtype))
    return jax.tree.unflatten(treedef, fused)


def accumulate(
    acc: Optional[Pytree],
    update: Pytree,
    weight: float,
    *,
    bn: Optional[int] = None,
) -> Pytree:
    """Streaming (incremental) fusion: acc <- acc + weight*update.

    This is the eager/JIT aggregator's inner operation: each arriving update
    is folded into the running fp32 accumulator with the pair_fuse kernel,
    so aggregation state is one model-sized buffer regardless of K."""
    if acc is None:
        return jax.tree.map(
            lambda u: (u.astype(jnp.float32) * weight), update
        )
    return jax.tree.map(
        lambda a, u: pair_fuse(
            a.reshape(-1), u.astype(jnp.float32).reshape(-1),
            op="wsum", wa=1.0, wb=float(weight),
            interpret=interpret_mode(),
            **_tile_kwargs(bn),
        ).reshape(a.shape),
        acc,
        update,
    )


def fuse_quantized(
    q_updates: Sequence[Pytree],
    scales: Sequence[Pytree],
    weights: Optional[Sequence[float]] = None,
    *,
    bn: Optional[int] = None,
    kb: Optional[int] = None,
) -> Pytree:
    """Fuse int8-quantised updates (beyond-paper comm compression).

    q_updates: K pytrees of int8 leaves; scales: K pytrees of scalar scales.
    """
    k = len(q_updates)
    if weights is None:
        weights = [1.0 / k] * k
    treedef = jax.tree.structure(q_updates[0])
    qs = [jax.tree.leaves(u) for u in q_updates]
    ss = [jax.tree.leaves(s) for s in scales]
    fused = []
    for i in range(len(qs[0])):
        stack = jnp.stack([l[i].reshape(-1) for l in qs])  # (K, N) int8
        sc = jnp.asarray(
            [float(ss[j][i]) * weights[j] for j in range(k)], jnp.float32
        )
        out = quant_agg(stack, sc, interpret=interpret_mode(),
                        **_tile_kwargs(bn, kb))
        fused.append(out.reshape(qs[0][i].shape))
    return jax.tree.unflatten(treedef, fused)


def quantize_update(update: Pytree) -> tuple[Pytree, Pytree]:
    """Party-side int8 quantisation of a model update (per-leaf scales)."""
    qs, ss = [], []
    leaves, treedef = jax.tree.flatten(update)
    for l in leaves:
        q, s = quantize(l)
        qs.append(q.reshape(l.shape))
        ss.append(s)
    return jax.tree.unflatten(treedef, qs), jax.tree.unflatten(treedef, ss)
