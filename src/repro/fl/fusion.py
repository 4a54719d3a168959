"""Model-update fusion algorithms (the aggregation ⊕ of §2.1).

All are coordinate-wise over the flattened update vectors and LINEAR in the
updates — the property JIT aggregation exploits: partial aggregates can be
checkpointed and resumed, and updates can be fused incrementally in any
order with the same result (tests/test_fusion.py proves both).

  FedAvg  — dataset-size-weighted mean of party weights.
  FedSGD  — mean of party gradients, applied by the server optimizer.
  FedProx — server-side fusion identical to FedAvg (the proximal term
            mu/2*||w - w_global||^2 modifies the PARTY loss; see party.py).

The streaming path runs one compiled program per fold (``accumulate``) and
one per finished round (``FusionState.finish``: the mean and the
algorithm's ``apply`` together), whatever the number of leaves.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels import SlabAcc, accumulate, fuse_updates
from repro.obs import span

Pytree = Any


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

    ``acc`` is an fp32 slab accumulator (`repro.kernels.SlabAcc`) that
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

    def fuse(self, updates: Sequence[Pytree], n_examples: Sequence[int]
             ) -> Pytree:
        ws = [self.weight_of(n) for n in n_examples]
        total = sum(ws)
        return fuse_updates(updates, [w / total for w in ws])

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
