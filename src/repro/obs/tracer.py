"""Per-round communication spans with live cost-model drift.

A :class:`Tracer` hooks the same executor-round boundaries the fault
harness guards (``repro.distributed.faults``): one **round span** per
``DistProblem.sddmm/spmm/spmm_t/fusedmm`` call, subdivided into one
**event span** per entry of the family's ``schedule_events`` — the
gather/phase/shift/reduce coordinates every family module exports.  Each
event span carries the collective kind it compiles to and its *modeled*
wire words (``schedule_words``, impl-exact for dense wire formats); the
round span carries the *measured* per-device wire words parsed out of
the compiled HLO (``repro.roofline.hlo_parse.wire_words``) and their
ratio — **cost-model drift**, 1.0 when the closed-form model matches the
wire exactly.  Support-pruned (``comm="sparse"``) rounds trace without
modeled words: their volume is data-dependent and drift is undefined.

Timing: the round's wall time is measured on the host clock.  A jitted
round is one XLA program, so its events have no host-side times of
their own; where each collective ran is read from a ``jax.profiler``
trace of the device, beside the program's host spans (``repro.obs.span``).

Zero-cost when disabled, like ``faults.guard``: no tracer is installed
by default and the api layer pays one module attribute read per call.
This module imports no jax; HLO measurement happens through the
problem's own ``lower_*`` methods, cached per program signature.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import List, Optional

from repro.obs import metrics as _metrics

__all__ = ["EventSpan", "RoundSpan", "Tracer", "active", "trace"]


@dataclasses.dataclass
class EventSpan:
    """One schedule event inside a round: a fault-harness coordinate."""
    point: str                    # gather | phase | shift | reduce
    phase: int
    kind: Optional[str]           # HLO collective, None for compute
    words: Optional[float]        # modeled wire words (None: no model)


@dataclasses.dataclass
class RoundSpan:
    """One guarded executor call, subdivided into its schedule events."""
    op: str
    family: str
    elision: str
    comm: str
    p: int
    c: int
    round: int                    # per-op call counter since tracing began
    session: bool
    dur: float                    # measured seconds of the call
    events: List[EventSpan]
    modeled_words: Optional[float]      # sum of event models (dense only)
    measured_words: Optional[dict]      # wire_words() dict, if measured
    drift: Optional[float]              # measured total / modeled total
    error: Optional[str] = None         # exception type, if the round died


_LOWER = {"sddmm": "lower_sddmm", "spmm": "lower_spmm",
          "spmm_t": "lower_spmm_t"}


class Tracer:
    """Collects :class:`RoundSpan`s; arm with :func:`trace`.

    ``measure_wire=True`` (default) lowers + compiles each distinct
    program signature once to parse its actual per-device wire words —
    amortized across calls by a signature-keyed cache, but still one
    extra XLA compile per signature; long-running serving loops can pass
    ``False`` and keep modeled words only.  ``registry`` (default: the
    armed ``obs.metrics`` registry, if any) receives round latency
    histograms and live drift gauges as the trace runs.
    """

    def __init__(self, *, measure_wire: bool = True,
                 registry: Optional[_metrics.MetricsRegistry] = None,
                 clock=time.perf_counter):
        self.rounds: List[RoundSpan] = []
        self.measure_wire = measure_wire
        self._registry = registry
        self._clock = clock
        self._counts: dict = {}
        self._wire_cache: dict = {}

    # -- measurement ---------------------------------------------------------
    def _measure(self, problem, op, elision, session):
        sig = (problem.alg.name, id(problem.grid), op, elision,
               problem.m, problem.n, problem.r, problem.nnz,
               problem.comm, problem.compress, session is not None)
        if sig not in self._wire_cache:
            from repro.roofline.hlo_parse import wire_words
            if op == "fusedmm":
                low = problem.lower_fusedmm(elision, session=session)
            else:
                low = getattr(problem, _LOWER[op])(session=session)
            self._wire_cache[sig] = wire_words(low.compile().as_text())
        return self._wire_cache[sig]

    # -- the round hook ------------------------------------------------------
    @contextlib.contextmanager
    def round(self, problem, op: str, elision: str = "none",
              session=None):
        """Span one executor round (called by the api layer)."""
        rnd = self._counts.get(op, 0)
        self._counts[op] = rnd + 1
        t0 = self._clock()
        err = None
        try:
            yield
        except BaseException as e:
            err = type(e).__name__
            raise
        finally:
            dur = self._clock() - t0
            self._finish(problem, op, elision, session, rnd, dur, err)

    def _finish(self, problem, op, elision, session, rnd, dur, err):
        events = problem.alg.schedule_events(problem, op, elision)
        words = problem.alg.schedule_words(problem, op, elision,
                                           session=session)
        measured = drift = None
        total = None if words is None else sum(w for *_, w in words)
        if err is None and self.measure_wire:
            try:
                measured = self._measure(problem, op, elision, session)
            except Exception:
                measured = None         # lowering unsupported: trace on
            if measured is not None and total:
                drift = measured["total"] / total
        spans = ([EventSpan(point, phase, None, None)
                  for point, phase in events] if words is None
                 else [EventSpan(*w) for w in words])
        self.rounds.append(RoundSpan(
            op=op, family=problem.alg.name, elision=elision,
            comm=problem.comm, p=problem.p, c=problem.c, round=rnd,
            session=session is not None, dur=dur, events=spans,
            modeled_words=total, measured_words=measured, drift=drift,
            error=err))
        reg = self._registry or _metrics.active()
        if reg is not None:
            lab = dict(op=op, family=problem.alg.name)
            reg.observe("executor.round_seconds", dur, **lab)
            reg.inc("executor.rounds", 1, **lab)
            if drift is not None:
                reg.gauge("costmodel.drift", drift, **lab)

    # -- reading -------------------------------------------------------------
    def drifts(self) -> List[float]:
        """All defined per-round drift ratios, trace order."""
        return [r.drift for r in self.rounds if r.drift is not None]


_ACTIVE: Optional[Tracer] = None


def active() -> Optional[Tracer]:
    """The armed tracer, or None (the zero-cost disabled state)."""
    return _ACTIVE


@contextlib.contextmanager
def trace(tracer: Optional[Tracer] = None, **kw):
    """Arm a tracer for the dynamic extent of the context.

    Yields the :class:`Tracer`; nesting restores the previous one on
    exit — same discipline as ``faults.inject``."""
    global _ACTIVE
    tr = Tracer(**kw) if tracer is None else tracer
    prev = _ACTIVE
    _ACTIVE = tr
    try:
        yield tr
    finally:
        _ACTIVE = prev
