"""Observability layer for the serving stack.

One bundle — :class:`Observability` — carries the three telemetry
surfaces threaded through ``launch/serve.py`` and friends:

* ``registry`` (:mod:`repro.obs.metrics`): counters / gauges /
  histograms with labels, ``snapshot()`` and a Prometheus-text exporter;
* ``tracer`` (:mod:`repro.obs.trace`): per-request lifecycle spans
  (TTFT, TPOT, queue wait, preemption/replay overhead);
* ``timeline`` (:mod:`repro.obs.timeline`): the ring-buffered per-tick
  scheduler event log that replaced ``BatchedServer.events``;
* ``span(name)`` (:mod:`repro.obs.span`): named spans of the serving
  loop on the profiler's clock, on exactly when the tracer is live.

``Observability.disabled()`` swaps registry and tracer for no-ops (and
so turns spans off) but keeps a REAL timeline: the ``server.events``
compat shim and the drop accounting must behave identically in both
modes, and the bit-identity test (tests/test_obs.py) pins that enabled
vs. disabled telemetry produces the same greedy streams and compile
counts.
"""
from __future__ import annotations

from .metrics import (DEFAULT_TIME_BUCKETS, NullRegistry, Registry,
                      global_registry, parse_prometheus,
                      reset_global_registry)
from .timeline import DEFAULT_CAP, Timeline, read_jsonl
from .trace import NullTracer, Span, Tracer
from .profile import JaxProfile, StepTimer, compile_counts, timeit
from .span import NO_SPAN, LoopSpan

__all__ = [
    "DEFAULT_CAP", "DEFAULT_TIME_BUCKETS", "JaxProfile", "NO_SPAN",
    "NullRegistry", "NullTracer", "Observability", "Registry", "Span",
    "StepTimer", "Timeline", "Tracer", "compile_counts", "global_registry",
    "parse_prometheus", "read_jsonl", "reset_global_registry", "timeit",
]


class Observability:
    """The telemetry bundle a :class:`BatchedServer` owns."""

    def __init__(self, *, registry: Registry | None = None,
                 tracer: Tracer | None = None,
                 timeline: Timeline | None = None,
                 trace_cap: int = DEFAULT_CAP,
                 const_labels: dict | None = None):
        if registry is None:
            registry = Registry(const_labels=const_labels)
        self.registry = registry
        self.tracer = tracer if tracer is not None else Tracer()
        self.timeline = (timeline if timeline is not None
                         else Timeline(cap=trace_cap))
        self.step_timer = StepTimer(self.registry)
        self._spans = self.tracer.enabled

    @classmethod
    def disabled(cls, *, trace_cap: int = DEFAULT_CAP) -> "Observability":
        """No-op registry/tracer, real timeline (events shim keeps working)."""
        return cls(registry=NullRegistry(), tracer=NullTracer(),
                   timeline=Timeline(cap=trace_cap))

    @property
    def enabled(self) -> bool:
        return self.registry.enabled

    def span(self, name: str, **fields):
        """A named span of the serving loop (see :mod:`repro.obs.span`):
        a profiler annotation with monotonic ends, live exactly when the
        tracer is; otherwise the shared no-op :data:`NO_SPAN`."""
        if not self._spans:
            return NO_SPAN
        return LoopSpan(name, fields)

    def dump_metrics(self, path) -> None:
        """Write the Prometheus-text snapshot (``--metrics-out``)."""
        self.registry.dump(path)

    def dump_trace(self, path) -> int:
        """Write the timeline JSONL (``--trace-out``); returns records."""
        return self.timeline.to_jsonl(path)
