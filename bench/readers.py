"""Arithmetic the metric readers share.  Each reader in ``metrics/`` is
one call of these on the run, so that a metric of a later cell is a new
small file."""
from __future__ import annotations

from bench import peaks, work
from bench.harness import Run, info


def per_unit(run: Run, seconds: float, unit: str, scale: float = 1.0):
    """``seconds`` per completed ``unit`` of the window, times ``scale``;
    None where the window completed none."""
    done = run.counters.get(unit, 0)
    return seconds / done * scale if done else None


def kernel_ms(run: Run, unit: str):
    """Device time of the Pallas kernels per ``unit``, on the busiest
    device; None where the trace holds no kernel."""
    if run.trace is None or not run.trace.kernel_s_max:
        return None
    return per_unit(run, run.trace.kernel_s_max, unit, 1e3)


def kernels_roofline(run: Run):
    """The least time of the window's algorithmic work on the run's
    chips (``work.py``) over the busiest device's kernel time, in %."""
    if run.trace is None or not run.trace.kernel_s_max:
        return None
    least, bound = work.least_seconds(
        work.Work(run.work["flops"], run.work["bytes"]),
        peaks.peak(run.device_kind), run.chips)
    info(roofline_bound=bound, least_s=least,
         kernel_s_max=run.trace.kernel_s_max)
    return 100 * least / run.trace.kernel_s_max


def device_idle_pct(run: Run):
    """1 - busy / traced window, as a mean over the devices, in %."""
    if run.trace is None:
        return None
    return 100 * (1 - run.trace.busy_s / run.trace.window_s)

