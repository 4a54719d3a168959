"""jit'd public wrappers over the Pallas kernels, operating on model-update
PYTREES (the paper's "list of one-dimensional vectors, one per layer").

The batch drains (``fuse_updates``, ``fuse_quantized``) fuse leaf by leaf:
each leaf is flattened, fused by its kernel and reshaped back. The streaming
fold (``accumulate``) keeps its accumulator flat (``FlatAcc``: every leaf end
to end in one fp32 vector) and folds a whole update in one compiled program,
in place: the update is staged end to end at its own width (a bf16 update as
bf16) and upcast to fp32 where the kernel reads it. The kernel mode is
decided here, once, from the backend (`interpret_mode`): on the CPU backend
the Pallas kernel bodies run in the interpreter, on a TPU they are compiled.
"""
from __future__ import annotations

import functools
import math
from typing import Any, List, Optional, Sequence, Tuple

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


@jax.tree_util.register_pytree_node_class
class FlatAcc:
    """The streaming accumulator: one 1-D fp32 array holding every leaf of a
    model update end to end, in ``jax.tree.leaves`` order, with no padding,
    whatever the update's dtype.

    Its layout (the update's tree structure and leaf shapes) is static pytree
    data, so a FlatAcc passes through ``jax.jit`` as one array, a program is
    compiled once per layout, and a checkpoint of it is the value alone."""

    def __init__(self, flat: jax.Array, treedef, shapes: Tuple[tuple, ...]):
        self.flat = flat
        self.treedef = treedef
        self.shapes = shapes

    def tree_flatten(self):
        return (self.flat,), (self.treedef, self.shapes)

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(children[0], *layout)

    def tree(self) -> Pytree:
        """The accumulator as the update's tree of fp32 leaves: a slice and
        a reshape per leaf, meant to run inside a jitted program."""
        leaves, off = [], 0
        for shape in self.shapes:
            n = math.prod(shape)
            leaves.append(self.flat[off:off + n].reshape(shape))
            off += n
        return jax.tree.unflatten(self.treedef, leaves)


def _flat(leaves: List[jax.Array]) -> jax.Array:
    """The leaves end to end in one 1-D array of their own dtype (a mixed
    tree's promoted one): a bf16 update is staged as bf16, not as fp32."""
    dtype = jnp.result_type(*(l.dtype for l in leaves))
    return jnp.concatenate([l.reshape(-1).astype(dtype) for l in leaves])


@functools.lru_cache(maxsize=64)
def _layout(sig: Tuple[tuple, ...]) -> Tuple[Tuple[tuple, ...], str, int]:
    """For an update whose leaves have ``sig`` (shape and dtype each): its
    leaf shapes, and what a fold reads of it, the staged dtype's name and
    the update's bytes. Cached per layout: a fold finds them in the one pass
    over its leaves that it makes anyway."""
    shapes = tuple(tuple(s) for s, _ in sig)
    nbytes = sum(math.prod(s) * d.itemsize for s, d in sig)
    return shapes, jnp.result_type(*(d for _, d in sig)).name, nbytes


@jax.jit
def first_fold(leaves: List[jax.Array], weight) -> jax.Array:
    """The first update of a round, flat and weighted: ``weight * u``,
    multiplied in fp32 as every later fold is."""
    return _flat(leaves).astype(jnp.float32) * weight


@functools.partial(jax.jit, donate_argnums=0,
                   static_argnames=("bn", "interpret"))
def fold_into(acc: jax.Array, leaves: List[jax.Array], w: jax.Array, *,
              bn: Optional[int] = None, interpret: bool) -> jax.Array:
    """``w[0] * acc + w[1] * u`` for the flat update ``u``, by one
    ``pair_fuse`` written into ``acc``'s buffer: ``acc`` is donated, the
    update is not. ``u`` stays at the update's width: the kernel upcasts it
    to fp32 as it reads it. Both weights arrive at run time, as the kernel
    reads them, so no compiler folds the ``1.0 *`` away and rounds
    differently."""
    return pair_fuse(acc, _flat(leaves), op="wsum", wa=w[0], wb=w[1],
                     alias_a=True, interpret=interpret, **_tile_kwargs(bn))


def accumulate(
    acc: Optional[FlatAcc],
    update: Pytree,
    weight: float,
    *,
    bn: Optional[int] = None,
    span=None,
) -> FlatAcc:
    """Streaming (incremental) fusion: acc <- acc + weight*update.

    This is the eager/JIT aggregator's inner operation: each arriving update
    is folded into the running flat fp32 accumulator by one compiled program
    (the update's leaves concatenated, then one pair_fuse), so aggregation
    state is one model-sized buffer regardless of K. ``acc`` is consumed:
    its buffer is donated and holds the result. ``update`` may also be a
    FlatAcc of the same layout (merging two partial aggregates). The weight
    is a traced argument: no weight compiles a program of its own. An open
    ``repro.obs.span`` given as ``span`` is told what the fold read: the
    staged ``dtype`` and the update's ``nbytes``."""
    if isinstance(update, FlatAcc):
        leaves, treedef, shapes = [update.flat], update.treedef, update.shapes
        dtype, nbytes = update.flat.dtype.name, update.flat.nbytes
    else:
        leaves, treedef = jax.tree.flatten(update)
        shapes, dtype, nbytes = _layout(
            tuple((l.shape, l.dtype) for l in leaves))
    if span is not None:
        span.set_metadata(dtype=dtype, nbytes=nbytes)
    if acc is None:
        return FlatAcc(first_fold(leaves, float(weight)), treedef, shapes)
    if treedef != acc.treedef or shapes != acc.shapes:
        raise ValueError(
            f"update layout {treedef} {shapes} does not match the "
            f"accumulator's {acc.treedef} {acc.shapes}")
    flat = fold_into(acc.flat, leaves, np.asarray([1.0, weight], np.float32),
                     bn=bn, interpret=interpret_mode())
    return FlatAcc(flat, treedef, shapes)


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
