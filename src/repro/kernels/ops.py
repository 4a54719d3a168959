"""jit'd public wrappers over the Pallas kernels, and the streaming fold,
operating on model-update PYTREES (the paper's "list of one-dimensional
vectors, one per layer").

The batch drains (``fuse_updates``, ``fuse_quantized``) fuse leaf by leaf:
each leaf is flattened, fused by its kernel and reshaped back. The streaming
fold (``accumulate``) keeps its accumulator in fp32 slabs (``SlabAcc``):
each leaf of ``PACK_BELOW`` elements or more a slab in its own shape, the
smaller ones end to end in one 1-D slab. It folds a whole update in one
compiled program with no kernel, one XLA multiply-add per slab written in
place into the donated accumulator: no leaf that has a slab of its own is
copied or relaid out, and the update is read at its own width (a bf16
update as bf16) and upcast to fp32 inside the multiply-add. The kernel
mode is decided here, once, from the backend (`interpret_mode`): on the
CPU backend the Pallas kernel bodies run in the interpreter, on a TPU they
are compiled.
"""
from __future__ import annotations

import functools
import math
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels.fused_agg import fused_agg
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


#: Leaves of fewer elements than this are packed end to end into one 1-D
#: fp32 slab; every larger leaf is a slab of its own, in its own shape.
#: Below it, copying a leaf into the packed slab (a relayout of a tiled
#: 2-D or 4-D leaf) costs less than a fusion of its own in every fold
#: program: its trace, its share of the executable and one more buffer a
#: call. Above it the copy of the leaf costs more than its fusion.
PACK_BELOW = 1 << 16


class Plan(NamedTuple):
    """Where each leaf of an update lives among the accumulator's slabs:
    the leaves in ``whole`` are slabs of their own, in leaf order, and the
    leaves in ``packed`` lie end to end in one last 1-D slab."""

    shapes: Tuple[tuple, ...]  # every leaf's, in ``jax.tree.leaves`` order
    whole: Tuple[int, ...]
    packed: Tuple[int, ...]

    @property
    def n_slabs(self) -> int:
        return len(self.whole) + bool(self.packed)


@functools.lru_cache(maxsize=64)
def slab_plan(shapes: Tuple[tuple, ...]) -> Plan:
    """The slab layout of an update whose leaves have ``shapes``."""
    small = [math.prod(s) < PACK_BELOW for s in shapes]
    return Plan(shapes, tuple(i for i, p in enumerate(small) if not p),
                tuple(i for i, p in enumerate(small) if p))


@jax.tree_util.register_pytree_node_class
class SlabAcc:
    """The streaming accumulator: a model update's leaves in fp32 slabs,
    whatever the update's dtype, laid out by ``slab_plan``: each large leaf
    a slab in its own shape, the small ones end to end in one 1-D slab.

    The tree structure and the plan are static pytree data, so a SlabAcc
    passes through ``jax.jit`` as its slabs, a program is compiled once per
    layout, and a checkpoint of it is the value alone."""

    def __init__(self, slabs: Tuple[jax.Array, ...], treedef, plan: Plan):
        self.slabs = tuple(slabs)
        self.treedef = treedef
        self.plan = plan

    def tree_flatten(self):
        return self.slabs, (self.treedef, self.plan)

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(children, *layout)

    def tree(self) -> Pytree:
        """The accumulator as the update's tree of fp32 leaves: the whole
        slabs as they are, the small leaves sliced out of the packed one;
        meant to run inside a jitted program."""
        plan = self.plan
        leaves = [None] * len(plan.shapes)
        for i, slab in zip(plan.whole, self.slabs):
            leaves[i] = slab
        off = 0
        for i in plan.packed:
            shape = plan.shapes[i]
            n = math.prod(shape)
            leaves[i] = lax.reshape(
                lax.slice(self.slabs[-1], (off,), (off + n,)), shape)
            off += n
        return jax.tree.unflatten(self.treedef, leaves)


def _slabs(leaves: Sequence[jax.Array], plan: Plan) -> List[jax.Array]:
    """The update's slabs at its own width: the whole leaves as they lie,
    the small ones end to end in their promoted dtype (a bf16 update's as
    bf16)."""
    out = [leaves[i] for i in plan.whole]
    if plan.packed:
        small = [leaves[i] for i in plan.packed]
        dtype = jnp.result_type(*(l.dtype for l in small))
        out.append(lax.concatenate(
            [lax.convert_element_type(lax.reshape(l, (l.size,)), dtype)
             for l in small], 0))
    return out


def _times(w: jax.Array, u: jax.Array) -> jax.Array:
    """``w * u`` in fp32 for an fp32 scalar ``w``, the update upcast where
    it is read."""
    u = lax.convert_element_type(u, jnp.float32)
    return lax.mul(lax.broadcast(w, u.shape), u)


@functools.lru_cache(maxsize=64)
def _layout(sig: Tuple[tuple, ...]) -> Tuple[Plan, str, int]:
    """For an update whose leaves have ``sig`` (shape and dtype each): its
    slab plan, and what a fold reads of it, the update's dtype name (a
    mixed tree's promoted one) and bytes. Cached per layout: a fold finds
    them in the one pass over its leaves that it makes anyway."""
    plan = slab_plan(tuple(tuple(s) for s, _ in sig))
    nbytes = sum(math.prod(s) * d.itemsize for s, d in sig)
    return plan, jnp.result_type(*(d for _, d in sig)).name, nbytes


@jax.jit
def first_fold(leaves: List[jax.Array], weight: jax.Array
               ) -> Tuple[jax.Array, ...]:
    """The first update of a round as the slabs of ``weight * u``,
    multiplied in fp32 as every later fold is."""
    plan = slab_plan(tuple(l.shape for l in leaves))
    return tuple(_times(weight, u) for u in _slabs(leaves, plan))


@functools.partial(jax.jit, donate_argnums=0)
def fold_into(acc: SlabAcc, update, w: jax.Array) -> SlabAcc:
    """``acc + w * u``, slab by slab, one XLA multiply-add each, written
    into ``acc``'s donated buffers; the update is not donated. ``update``
    is the update's leaves, read at their own width and upcast to fp32
    inside the multiply-add, or a partial aggregate's SlabAcc of the same
    layout. The weight arrives at run time, so no compiler folds it into
    the program and rounds differently."""
    us = (update.slabs if isinstance(update, SlabAcc)
          else _slabs(update, acc.plan))
    return SlabAcc(tuple(lax.add(a, _times(w, u))
                         for a, u in zip(acc.slabs, us)),
                   acc.treedef, acc.plan)


def accumulate(
    acc: Optional[SlabAcc],
    update: Pytree,
    weight: float,
    *,
    span=None,
) -> SlabAcc:
    """Streaming (incremental) fusion: acc <- acc + weight*update.

    This is the eager/JIT aggregator's inner operation: each arriving update
    is folded into the running fp32 slab accumulator by one compiled
    program (``fold_into``: one multiply-add per slab, no kernel), so
    aggregation state is one model's worth of fp32 regardless of K.
    ``acc`` is consumed: its buffers are donated and hold the result.
    ``update`` may also be a SlabAcc of the same layout (merging two
    partial aggregates). The weight is a traced argument: no weight
    compiles a program of its own. An open ``repro.obs.span`` given as
    ``span`` is told what the fold read: the update's ``dtype`` and
    ``nbytes``, and the accumulator's ``slabs`` (its buffers) and
    ``packed`` (the leaves in its packed slab)."""
    if isinstance(update, SlabAcc):
        leaves, treedef, plan = update, update.treedef, update.plan
        dtype, nbytes = "float32", sum(s.nbytes for s in update.slabs)
    else:
        leaves, treedef = jax.tree.flatten(update)
        plan, dtype, nbytes = _layout(
            tuple((l.shape, l.dtype) for l in leaves))
    if span is not None:
        span.set_metadata(dtype=dtype, nbytes=nbytes, slabs=plan.n_slabs,
                          packed=len(plan.packed))
    w = np.float32(weight)
    if acc is None:
        return SlabAcc(first_fold(leaves, w), treedef, plan)
    if treedef != acc.treedef or plan != acc.plan:
        raise ValueError(
            f"update layout {treedef} {plan.shapes} does not match the "
            f"accumulator's {acc.treedef} {acc.plan.shapes}")
    return fold_into(acc, leaves, w)


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
