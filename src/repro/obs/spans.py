"""``repro.obs.spans`` — the data plane's host spans, on the profiler's clock.

``span(name, **args)`` opens ``repro.<name>`` as a
``jax.profiler.TraceAnnotation``: while a JAX profiler trace is being taken
it lands on the calling thread's host line, on the same clock as the
device's programs and operations; otherwise it does nothing. The JAX
profiler records and writes these spans; there is no recorder, buffer or
flag here. Arguments are evaluated even when no trace is being taken, so
pass values the caller already holds, never one computed by walking a
pytree.

The aggregator's served path opens three (``fl/aggregator.py``,
``fl/fusion.py``):

  repro.drain(round=r)         ``AggregationExecutor.drain``: poll, weight,
                               fold and commit
  repro.fold(dtype=, nbytes=,  ``FusionState.fold``: one per update folded,
             slabs=, packed=)  around ``accumulate``'s dispatch of
                               one compiled program for the whole update;
                               what it read, the update's dtype and bytes,
                               and the accumulator's buffers and the leaves
                               packed into one of them, cached per layout
  repro.finish_round(round=r)  ``AggregationExecutor.finish_round``:
                               ``FusionState.finish`` (the mean and
                               ``apply``, one compiled program) and
                               ``publish_fused``
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "repro."


def span(name: str, **args) -> TraceAnnotation:
    """A context manager: the host span ``repro.<name>`` with ``args``."""
    return TraceAnnotation(PREFIX + name, **args)
