"""Model-update fusion algorithms (the aggregation ⊕ of §2.1).

All are coordinate-wise over the flattened update vectors and LINEAR in the
updates — the property JIT aggregation exploits: partial aggregates can be
checkpointed and resumed, and updates can be fused incrementally in any
order with the same result (tests/test_fusion.py proves both).

  FedAvg  — dataset-size-weighted mean of party weights.
  FedSGD  — mean of party gradients, applied by the server optimizer.
  FedProx — server-side fusion identical to FedAvg (the proximal term
            mu/2*||w - w_global||^2 modifies the PARTY loss; see party.py).

This module owns the aggregator's fold and the accumulator's format. The
streaming fold (``accumulate``) keeps its accumulator in fp32 slabs
(``SlabAcc``): each leaf of ``PACK_BELOW`` elements or more a slab in its
own shape, the smaller ones end to end in one 1-D slab. It folds a whole
update in one compiled program with no kernel, one XLA multiply-add per
slab written in place into the donated accumulator: no leaf that has a
slab of its own is copied or relaid out, and the update is read at its own
width (a bf16 update as bf16) and upcast to fp32 inside the multiply-add.
A finished round is one more program (``FusionState.finish``: the mean and
the algorithm's ``apply`` together), whatever the number of leaves.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs import span

Pytree = Any


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


@functools.partial(jax.jit, static_argnames=("dtype",))
def weighted_mean(acc: SlabAcc, total_weight, dtype=None) -> Pytree:
    """The accumulator over the total weight, as the updates' tree."""
    return jax.tree.map(lambda a: (a / total_weight).astype(dtype or a.dtype),
                        acc.tree())


@functools.partial(jax.jit, static_argnums=0)
def finished_model(alg: "FusionAlgorithm", acc: SlabAcc, total_weight,
                   global_model: Pytree, lr) -> Pytree:
    """The round's new global model: the mean and ``alg.apply`` in one
    program; the total weight and the step are traced."""
    return alg.apply(global_model, weighted_mean(acc, total_weight), lr)


@dataclasses.dataclass
class FusionState:
    """Checkpointable partial aggregate: fp32 accumulator + total weight.

    ``acc`` is an fp32 slab accumulator (``SlabAcc``) that
    this state owns: ``fold`` and ``merge`` donate its buffers to the state
    they return, and this state is then spent: using it again raises. A
    snapshot that must outlive the next fold holds a copy
    (``AggregationExecutor.checkpoint``)."""

    acc: Optional[SlabAcc] = None
    total_weight: float = 0.0
    n_fused: int = 0
    spent: bool = dataclasses.field(default=False, init=False,
                                    repr=False, compare=False)

    def _check_live(self) -> None:
        if self.spent:
            raise RuntimeError(
                "this FusionState was folded or merged into a new state, "
                "which owns its accumulator now; use that state")

    def _folded(self) -> SlabAcc:
        self._check_live()
        if self.acc is None or self.total_weight <= 0:
            raise ValueError("no update has been folded")
        return self.acc

    def fold(self, update: Pytree, weight: float) -> "FusionState":
        with span("fold") as s:
            self._check_live()
            acc = accumulate(self.acc, update, weight, span=s)
            self.spent = True
            return FusionState(acc, self.total_weight + weight,
                               self.n_fused + 1)

    def merge(self, other: "FusionState") -> "FusionState":
        """Merge two partial aggregates (parallel aggregation). Donates this
        state's accumulator, never ``other``'s."""
        self._check_live()
        other._check_live()
        if self.acc is None:
            return other
        if other.acc is None:
            return self
        acc = accumulate(self.acc, other.acc, 1.0)
        self.spent = True
        return FusionState(acc, self.total_weight + other.total_weight,
                           self.n_fused + other.n_fused)

    def result(self, dtype=None) -> Pytree:
        """The weighted mean, as the updates' tree (fp32 leaves unless
        ``dtype``), in one compiled program."""
        return weighted_mean(self._folded(), self.total_weight, dtype)

    def finish(self, alg: "FusionAlgorithm", global_model: Pytree,
               lr: float = 1.0) -> Pytree:
        """``alg.apply(global_model, self.result(), lr)`` in one compiled
        program per algorithm and layout: the new global model, in the
        global model's tree and dtypes (a bf16 model rounded once, from the
        fp32 mean)."""
        return finished_model(alg, self._folded(), self.total_weight,
                              global_model, lr)


class FusionAlgorithm:
    """Stateless: instances of one class are interchangeable, and compare
    and hash by class, so a finish program compiles once per class."""

    name = "base"
    server_side = "weights"  # what parties send: weights | gradients

    def __eq__(self, other) -> bool:
        return type(self) is type(other)

    def __hash__(self) -> int:
        return hash(type(self))

    def weight_of(self, n_examples: int) -> float:
        return float(max(n_examples, 1))

    def apply(self, global_model: Pytree, fused: Pytree, lr: float = 1.0
              ) -> Pytree:
        """Turn the fused quantity into the new global model."""
        return jax.tree.map(lambda g, f: f.astype(g.dtype), global_model, fused)


class FedAvg(FusionAlgorithm):
    name = "fedavg"


class FedProx(FusionAlgorithm):
    """Server side == FedAvg; the proximal term lives in the party loss."""

    name = "fedprox"


class FedSGD(FusionAlgorithm):
    """Parties send gradients; the server applies one SGD step."""

    name = "fedsgd"
    server_side = "gradients"

    def apply(self, global_model: Pytree, fused_grad: Pytree, lr: float = 1.0
              ) -> Pytree:
        return jax.tree.map(
            lambda w, g: (w.astype(jnp.float32) - lr * g.astype(jnp.float32)
                          ).astype(w.dtype),
            global_model,
            fused_grad,
        )


ALGORITHMS: Dict[str, FusionAlgorithm] = {
    a.name: a() for a in (FedAvg, FedProx, FedSGD)
}


def get_algorithm(name: str) -> FusionAlgorithm:
    return ALGORITHMS[name]
