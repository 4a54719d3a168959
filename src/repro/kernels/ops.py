"""The batch drains over the Pallas kernels, operating on model-update
PYTREES (the paper's "list of one-dimensional vectors, one per layer").

``fuse_updates`` and ``fuse_quantized`` fuse a batch of K updates leaf by
leaf: each leaf is stacked across the updates, flattened, fused by its
kernel and reshaped back. Nothing on the aggregator's served path calls
them: the served streaming fold is ``repro.fl.fusion.accumulate``. The
kernel mode is decided here, once, from the backend (`interpret_mode`): on
the CPU backend the Pallas kernel bodies run in the interpreter, on a TPU
they are compiled.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.fused_agg import fused_agg
from repro.kernels.quant_agg import quant_agg, quantize

Pytree = Any


def interpret_mode() -> bool:
    """Run the Pallas kernels interpreted only on the CPU backend; on any
    accelerator they compile (there is no fallback to the interpreter)."""
    return jax.default_backend() == "cpu"


def fuse_updates(
    updates: Sequence[Pytree],
    weights: Optional[Sequence[float]] = None,
) -> Pytree:
    """Weighted fusion of K model updates (FedAvg-style weighted mean when
    weights sum to 1). Leaf-wise: stacks each leaf across updates and runs
    the fused_agg kernel once per leaf, at its default tile."""
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
        out = fused_agg(stack, w, interpret=interpret_mode())
        fused.append(out.reshape(leaves[0][i].shape).astype(leaves[0][i].dtype))
    return jax.tree.unflatten(treedef, fused)


def fuse_quantized(
    q_updates: Sequence[Pytree],
    scales: Sequence[Pytree],
    weights: Optional[Sequence[float]] = None,
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
        out = quant_agg(stack, sc, interpret=interpret_mode())
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
