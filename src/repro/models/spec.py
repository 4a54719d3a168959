"""Single-source-of-truth parameter specs.

Every model defines ``param_specs(cfg) -> dict`` (a nested dict whose leaves
are :class:`TensorSpec`).  From that one tree we derive

  * randomly-initialised parameters      (:func:`init_params`)
  * the logical-axis tree                (:func:`axes_tree`)
  * NamedShardings via the rule table    (``launch/sharding.py``)

so parameters, logical axes and shardings can never drift apart.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    """Shape + logical axis names + init for one parameter tensor."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis name per dim (None = replicated)
    init: str = "normal"  # normal | zeros | ones | rglru_lambda
    scale: float = 1.0  # stddev multiplier for "normal"
    dtype: str = "bfloat16"
    # inputs each output sums over ("normal": std = scale / sqrt(fan_in));
    # None = the second-to-last dim, right for (in, out) matrices
    fan_in: Optional[int] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def stack_specs(specs: Pytree, n: int, axis_name: str = "layers") -> Pytree:
    """Add a leading stacked-layer dim of size ``n`` to every spec leaf."""

    def _stack(s: TensorSpec) -> TensorSpec:
        return dataclasses.replace(
            s, shape=(n,) + s.shape, axes=(axis_name,) + s.axes
        )

    return jax.tree.map(_stack, specs, is_leaf=lambda x: isinstance(x, TensorSpec))


def _init_leaf(key: jax.Array, s: TensorSpec) -> jax.Array:
    dt = jnp.dtype(s.dtype)
    if s.init == "zeros":
        return jnp.zeros(s.shape, dt)
    if s.init == "ones":
        return jnp.ones(s.shape, dt)
    if s.init == "rglru_lambda":
        # Griffin: a in [0.9, 0.999] -> Lambda = softplus^{-1}((-log a)/c), c=8.
        u = jax.random.uniform(key, s.shape, jnp.float32, 0.9, 0.999)
        lam = jnp.log(jnp.expm1(-jnp.log(u) / 8.0))
        return lam.astype(dt)
    fan_in = s.fan_in or (s.shape[-2] if len(s.shape) >= 2 else s.shape[-1])
    std = s.scale / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, s.shape, jnp.float32) * std).astype(dt)


def init_params(key: jax.Array, specs: Pytree) -> Pytree:
    """Materialise random parameters for a spec tree."""
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, TensorSpec)
    )
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [_init_leaf(k, s) for k, s in zip(keys, leaves)])


def abstract_params(specs: Pytree) -> Pytree:
    """ShapeDtypeStructs for a spec tree (for dry-runs: no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        specs,
        is_leaf=lambda x: isinstance(x, TensorSpec),
    )


def axes_tree(specs: Pytree) -> Pytree:
    """Logical-axis tuples, same structure as the params."""
    return jax.tree.map(
        lambda s: s.axes, specs, is_leaf=lambda x: isinstance(x, TensorSpec)
    )


def count_params(specs: Pytree) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, TensorSpec))
    return int(sum(np.prod(s.shape) for s in leaves))
