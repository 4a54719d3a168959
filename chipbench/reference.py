"""The plain reference of one aggregation round, and the comparison.

A round fuses K party updates into the weighted mean (weights: each
party's dataset size) and turns it into the new global model: FedSGD takes
one step ``w - lr * mean``, FedAvg and FedProx publish the mean itself.
This is straightforward ``jax.numpy``, leaf by leaf (one small program per
distinct leaf shape), with no kernels, and imports nothing of the program
under test. It sums in the order the updates were published, as the
streaming fold does.

``compute_dtype=bfloat16`` gives the benchmark's control: the same
arithmetic one precision below the configuration's float32, every step
rounded to it, which the comparison has to refuse.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _rounding(ct):
    """Rounds an f32 value to ``ct``'s precision at every step. XLA may
    keep a lower-precision intermediate in f32 (excess precision), so a
    cast alone would not make the control compute in ``ct``."""
    if jnp.dtype(ct) == jnp.float32:
        return lambda x: x
    fi = jnp.finfo(ct)
    return lambda x: jax.lax.reduce_precision(
        x, exponent_bits=fi.nexp, mantissa_bits=fi.nmant)


def _new_leaf(us, n_examples, prev, lr, algorithm, ct):
    r = _rounding(ct)
    f32 = lambda x: r(x.astype(jnp.float32))  # noqa: E731
    w = f32(jnp.maximum(n_examples, 1))
    total = r(jnp.sum(w))
    acc = r(f32(us[0]) * w[0])
    for j in range(1, len(us)):
        acc = r(acc + r(f32(us[j]) * w[j]))
    mean = r(acc / total)
    if algorithm == "fedsgd":
        new = r(f32(prev) - r(f32(lr) * mean))
    elif algorithm in ("fedavg", "fedprox"):
        new = mean
    else:
        raise ValueError(f"no reference for algorithm {algorithm!r}")
    return new.astype(prev.dtype)


@functools.partial(jax.jit, static_argnames=("algorithm", "compute_dtype"))
def reference_leaf(us, n_examples, prev, lr, *, algorithm: str,
                   compute_dtype=jnp.float32):
    """One leaf of the new global model, from that leaf of each update."""
    return _new_leaf(us, n_examples, prev, jnp.asarray(lr, jnp.float32),
                     algorithm, compute_dtype)


@functools.partial(jax.jit, static_argnames=("algorithm",))
def _leaf_gap(us, n_examples, prev, lr, got, *, algorithm: str):
    want = _new_leaf(us, n_examples, prev, jnp.asarray(lr, jnp.float32),
                     algorithm, jnp.float32).astype(jnp.float32)
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - want))
    return err, jnp.max(jnp.abs(want))


def reference_round(updates: Sequence[list], n_examples, prev: list,
                    lr: float, algorithm: str,
                    compute_dtype=jnp.float32) -> list:
    """The new global model from ``updates`` (in publish order)."""
    n = jnp.asarray(n_examples)
    return [reference_leaf(tuple(u[i] for u in updates), n, g, lr,
                           algorithm=algorithm, compute_dtype=compute_dtype)
            for i, g in enumerate(prev)]


def round_gap(updates: Sequence[list], n_examples, prev: list, lr: float,
              algorithm: str, published) -> float:
    """The widest gap between a published model and the reference's: over
    the leaves, the largest |published - reference| relative to the
    reference leaf's largest magnitude. Infinite where anything is not
    finite, or where the published model's leaves do not match."""
    if published is None or len(published) != len(prev) or any(
            g.shape != p.shape or g.dtype != p.dtype
            for g, p in zip(published, prev)):
        return float("inf")
    n = jnp.asarray(n_examples)
    pairs = [_leaf_gap(tuple(u[i] for u in updates), n, g, lr, published[i],
                       algorithm=algorithm) for i, g in enumerate(prev)]
    err, mag = (np.asarray(jax.device_get(x), np.float64)
                for x in zip(*pairs))
    rel = err / np.maximum(mag, np.finfo(np.float32).tiny)
    return float(np.max(rel)) if np.all(np.isfinite(rel)) else float("inf")
