"""``repro.obs`` — tracing, metrics, and the live dashboard.

Two clocks, one per plane:

* the control plane (scheduler, cluster, controller) traces in **sim
  time** with ``Tracer``: deterministic, reconciled with billing;
* the data plane (the aggregator folding updates on the device) opens
  **profiler-clock** spans with ``span(name, **args)`` (``repro.obs.spans``):
  ``jax.profiler.TraceAnnotation``s that land in a ``jax.profiler`` trace
  beside the device's own events.

``span`` is imported on first use, so the control plane imports this
package without JAX.

The sim-time tracer is zero-overhead when disabled: every component
defaults to the shared ``NULL_TRACER`` singleton and guards emission on
``tracer.enabled``.
Enable by passing a ``Tracer`` via ``Platform(tracer=...)`` or
``Platform.serve(..., trace=...)``; export with
``tracer.export_chrome(path)`` (Perfetto-loadable) and reconcile billing
with ``tracer.reconcile(cluster)``.
"""
from repro.obs.dashboard import DashboardView
from repro.obs.registry import Counter, Histogram, MetricsRegistry
from repro.obs.trace import NULL_TRACER, NullTracer, Span, TraceEvent, Tracer

__all__ = [
    "Counter",
    "DashboardView",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "Span",
    "TraceEvent",
    "Tracer",
    "span",
]


def __getattr__(name):
    if name == "span":
        from repro.obs.spans import span

        return span
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
