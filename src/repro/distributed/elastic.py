"""Elastic scaling, straggler mitigation, and failure handling.

Design for 1000+ nodes (CPU-simulated here, same control flow on TPU):

* **Checkpoint/restart** — every step is restartable from the last
  committed checkpoint (atomic rename + _COMMITTED marker).  The launcher
  wraps each step in ``run_step_resilient``: a *retryable* failure
  triggers restore-and-retry with exponential backoff; repeated failures
  raise after ``max_retries``.  Only errors ``faults.retryable`` admits are
  retried — a retry loop that swallows every ``Exception`` turns caller
  bugs (TypeError, shape mismatch) into silent infinite restores, so
  non-transient errors propagate on the first attempt.

* **Elastic re-mesh** — ``remesh``: given a new device count, recompute the
  mesh + shardings and device_put the restored pytrees.  Because all
  shardings derive from PartitionSpecs over named axes, a job can resume
  on a smaller/larger pod slice as long as divisibility holds (the
  standard slice-resize flow).  Whole-problem re-planning (degraded mesh,
  re-dispatched algorithm family) lives in ``repro.core.api.degrade``.

* **Straggler mitigation** — ``StepMonitor`` tracks a rolling median of
  step times; a step exceeding ``straggler_factor`` x median flags the
  step.  On real multi-host deployments the flagged host would be
  cordoned and the job re-meshed; here the hook fires a callback and the
  flagged step ids accumulate in ``monitor.flagged`` (tested
  deterministically with a fake clock).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import numpy as np
from jax.sharding import Mesh

from repro.distributed import faults
from repro.obs import metrics as obs_metrics




def backoff_delays(max_retries: int, *, base: float = 0.0,
                   factor: float = 2.0, max_delay: float = 2.0,
                   jitter: float = 0.25, seed: int = 0):
    """Deterministic exponential-backoff schedule with seeded jitter.

    Yields ``max_retries`` delays: ``min(base * factor**k, max_delay)``
    scaled by ``1 + jitter * U[0,1)`` from ``np.random.default_rng(seed)``
    — the same seed replays the same schedule, so retry timing is part of
    the reproducible record, not noise.
    """
    rng = np.random.default_rng(seed)
    d = base
    for _ in range(max_retries):
        yield min(d, max_delay) * (1.0 + jitter * float(rng.uniform()))
        d = d * factor if d > 0 else base


@dataclasses.dataclass
class StepMonitor:
    straggler_factor: float = 3.0
    window: int = 32
    clock: Callable[[], float] = time.monotonic
    on_straggler: Optional[Callable[[int, float, float], None]] = None
    _times: list = dataclasses.field(default_factory=list)
    #: step ids flagged as stragglers, in observation order
    flagged: list = dataclasses.field(default_factory=list)

    def observe(self, step: int, seconds: float) -> bool:
        """Record a step time; returns True if flagged as straggler."""
        med = float(np.median(self._times)) if self._times else None
        self._times.append(seconds)
        if len(self._times) > self.window:
            self._times.pop(0)
        reg = obs_metrics.active()
        if reg is not None:
            reg.observe("train.step_seconds", seconds)
        if med is not None and seconds > self.straggler_factor * med:
            self.flagged.append(step)
            if reg is not None:
                reg.inc("train.stragglers")
            if self.on_straggler:
                self.on_straggler(step, seconds, med)
            return True
        return False

    def timed(self, step: int, fn, *a, **kw):
        t0 = self.clock()
        out = fn(*a, **kw)
        jax.block_until_ready(out)
        self.observe(step, self.clock() - t0)
        return out


def remesh(n_devices: int, model_parallel: int):
    """Build a (data, model) mesh over the first n_devices devices."""
    devs = np.array(jax.devices())[:n_devices]
    assert n_devices % model_parallel == 0
    return Mesh(devs.reshape(n_devices // model_parallel, model_parallel),
                ("data", "model"))


def run_step_resilient(step_fn, save_fn, restore_fn, *args,
                       max_retries: int = 2, on_failure=None,
                       retryable=faults.RETRYABLE, backoff=None,
                       sleep=time.sleep):
    """Execute one training step with restore-and-retry semantics.

    step_fn dying with a *retryable* error (injected ``TransientFault``,
    runtime ``JaxRuntimeError`` from a preempted host or failed
    collective) triggers ``restore_fn() -> fresh args`` and a retry after
    an exponential-backoff delay; ``on_failure(attempt, err)`` sees the
    typed fault.  Non-retryable errors — TypeErrors, shape mismatches,
    compile refusals, out-of-memory, any caller bug — propagate
    immediately: retrying them can only fail the same way again.

    ``backoff`` is an iterable of delays (default: ``backoff_delays``
    with zero base delay, i.e. no sleeping in tests); ``sleep`` is
    injectable for deterministic tests.  ``restore_fn`` may return None
    to retry with the original args.
    """
    delays = iter(backoff if backoff is not None
                  else backoff_delays(max_retries))
    attempt = 0
    while True:
        try:
            return step_fn(*args)
        except retryable as e:
            typed = faults.unwrap(e)   # typed fault may be XLA-laundered
            if not faults.retryable(typed):
                raise                  # compile / memory failure: no retry
            attempt += 1
            if on_failure:
                on_failure(attempt, typed)
            if attempt > max_retries:
                raise
            d = next(delays, 0.0)
            if d > 0:
                sleep(d)
            fresh = restore_fn() if restore_fn is not None else None
            if fresh is not None:
                args = fresh
