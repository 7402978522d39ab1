"""Exporters: a text summary of traced rounds, and the metrics artifact.

``write_artifacts`` fixes the artifact convention consumed by
``benchmarks/run.py`` and CI: ``METRICS_<tag>.json``
(``MetricsRegistry.snapshot()``) in a chosen directory.  A timeline of
the program is a ``jax.profiler`` trace (docs/observability.md).
"""
from __future__ import annotations

import os

from repro.obs.metrics import MetricsRegistry
from repro.obs.tracer import Tracer

__all__ = ["round_summary", "write_artifacts"]


def round_summary(tracer: Tracer) -> str:
    """One line per traced round: words modeled vs measured, drift, time."""
    lines = [f"{'round':28s} {'comm':6s} {'modeled':>10s} {'measured':>10s} "
             f"{'drift':>8s} {'ms':>9s}"]
    for r in tracer.rounds:
        name = (f"{r.family}.{r.op}"
                + (f"[{r.elision}]" if r.op == "fusedmm" else "")
                + ("+sess" if r.session else "")
                + f"#{r.round}")
        mod = "-" if r.modeled_words is None else f"{r.modeled_words:.0f}"
        mea = "-" if r.measured_words is None \
            else f"{r.measured_words['total']:.0f}"
        dr = "-" if r.drift is None else f"{r.drift:.4f}"
        err = f"  ERROR={r.error}" if r.error else ""
        lines.append(f"{name:28s} {r.comm:6s} {mod:>10s} {mea:>10s} "
                     f"{dr:>8s} {r.dur * 1e3:9.3f}{err}")
    return "\n".join(lines)


def write_artifacts(out_dir: str, tag: str, *,
                    registry: MetricsRegistry) -> dict:
    """Write ``METRICS_<tag>.json``; returns ``{"metrics": path}``."""
    os.makedirs(out_dir, exist_ok=True)
    p = os.path.join(out_dir, f"METRICS_{tag}.json")
    with open(p, "w") as fh:
        fh.write(registry.to_json())
    return {"metrics": p}
