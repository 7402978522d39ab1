"""Observability: host spans on the profiler's clock, per-round comm
spans with live cost-model drift, metrics.

* :func:`repro.obs.span` — the program's host spans (``api.*``,
  ``als.*``), written into a ``jax.profiler`` trace beside the device's
  ops on one clock; nothing is recorded without a profiler session;
* :mod:`repro.obs.tracer` — ``with obs.trace() as tr:`` one round span
  per executor call at the boundaries the fault harness guards, with
  its measured duration and its modeled (``schedule_words``) vs
  measured (compiled-HLO) wire words and their ratio, **cost-model
  drift**, itemised per gather/phase/shift/reduce event;
* :mod:`repro.obs.metrics` — ``with obs.metrics.collect() as reg:``
  one labeled counter/gauge/histogram registry absorbing the repo's
  ad-hoc counters (Session, SessionPool, ElasticProblem, serving ticks,
  StepMonitor) with a JSON-exact snapshot.

The tracer and the registry are zero-cost when disabled (the
``faults.guard`` discipline: one module attribute read on the executor
hot path).  :mod:`repro.obs.export` prints a round summary and writes
the ``METRICS_<tag>.json`` artifact.  See docs/observability.md.
"""
from repro.obs import metrics
from repro.obs.export import round_summary, write_artifacts
from repro.obs.metrics import MetricsRegistry, collect
from repro.obs.spans import span
from repro.obs.tracer import EventSpan, RoundSpan, Tracer, active, trace

__all__ = [
    "EventSpan", "MetricsRegistry", "RoundSpan", "Tracer", "active",
    "collect", "metrics", "round_summary", "span", "trace",
    "write_artifacts",
]
