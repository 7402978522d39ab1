"""The benchmark's harness: finds a cell's parts by name and runs it once.

Everything that belongs to one configuration, traffic mix, cell or
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

    configs/<config>.json      sizes and source of one deployment
    traffic/<traffic>.json     parameters of one traffic mix; its
                               ``loop`` names the generator below
    loops/<loop>.py            one general generator of a kind of traffic
    cells/<workload>.json      the limits of the cell's correctness check
    metrics/<metric>.py        ``read(run)``: one metric, or None where the
                               run holds nothing for it to read

A later change adds a cell, configuration, traffic mix or metric as new
files plus new entries in ``BENCHMARK.json``, and edits no file here.
"""
from __future__ import annotations

import dataclasses
import faulthandler
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / "out"
STALL = 1.4


class Layout:
    """Where the parts live: the first of ``roots`` that holds a part by
    its name wins, so a test can drop new parts into a directory of its
    own in front of this one."""

    def __init__(self, spec: dict, roots=(BENCH,)):
        self.spec = spec
        self.roots = [Path(r) for r in roots]

    @classmethod
    def load(cls, path=ROOT / "BENCHMARK.json", roots=(BENCH,)):
        with open(path) as f:
            return cls(json.load(f), roots)

    def find(self, kind: str, name: str, ext: str) -> Path:
        for root in self.roots:
            path = root / kind / f"{name}{ext}"
            if path.is_file():
                return path
        raise LookupError(f"no {kind}/{name}{ext} under "
                          f"{[str(r) for r in self.roots]}")

    def json(self, kind: str, name: str) -> dict:
        with open(self.find(kind, name, ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        path = self.find(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise LookupError(f"no workload {name!r} in the benchmark")

    def metrics(self, workload: str, traced: bool):
        """The cell's metrics: end-to-end ones untraced, per-layer ones
        traced; a metric without ``workloads`` belongs to every cell."""
        group = self.spec["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if workload in m.get("workloads", [workload])]


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their numbers
    from here."""
    chips: int
    device_kind: str
    setup_s: float
    window_s: float
    #: units of work the window completed (``rounds``, ``matvecs``, ...)
    counters: dict
    #: algorithmic work of the window's kernel calls (``flops``,
    #: ``bytes``), from ``work.py``
    work: dict
    #: host seconds of the program's planning in set-up, where it plans
    host_plan_s: Optional[float] = None
    #: the trace's reduction (``trace.Reduction``) in a traced run
    trace: object = None


class CompileCounter:
    """Counts XLA backend compilations (a ``jax.monitoring`` listener,
    as ``chip_smoke.py`` counts them)."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.count = 0

        def on_event(event, duration, **kw):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


def require_chips(count: int):
    """The first ``count`` TPU devices; exits non-zero, before any result,
    on any other platform or with too few chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < count:
        sys.exit(f"bench: needs {count} TPU chip(s), found "
                 f"{len(devices)} x {devices[0].platform}")
    return devices[:count]


def memory_peak(devices) -> Optional[int]:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _checks(numbers: dict, limits: dict) -> dict:
    """Each compared number beside its limit; a number the cell has no
    limit for, or a limit with no number, is a fault of the harness."""
    if set(numbers) != set(limits):
        raise KeyError(f"compared {sorted(numbers)}, limits "
                       f"{sorted(limits)}")
    return {k: {"value": numbers[k], "limit": limits[k]}
            for k in sorted(numbers)}


def _correct(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def _watch_for_stall(ends: list):
    """Where a unit runs past ``STALL`` times the median unit before it,
    every thread's stack goes to standard error at that moment, so that
    a stall names the code it sat in."""
    if len(ends) >= 3:
        units = sorted(b - a for a, b in zip([0.0] + ends, ends))
        faulthandler.dump_traceback_later(STALL * units[len(units) // 2])


def info(**kw):
    """One line of what the run did, on standard error."""
    print("bench: " + json.dumps(kw), file=sys.stderr, flush=True)


def run_cell(layout: Layout, name: str, seed: int, seconds: float,
             traced: bool, devices, t0: float) -> dict:
    """Run one cell once on ``devices``; returns the result line.

    ``t0`` is the host clock at process start: set-up runs from it to
    the first timed call.
    """
    import jax
    from bench import trace as trace_mod

    cell = layout.workload(name)
    config = layout.json("configs", cell["config"])
    traffic = layout.json("traffic", cell["traffic"])
    limits = layout.json("cells", name)["limits"]
    loop = layout.module("loops", traffic["loop"]).Loop(
        config, traffic, devices, seed)
    compiles = CompileCounter()

    t = time.perf_counter()
    loop.plan()
    host_plan_s = time.perf_counter() - t
    loop.warm()
    info(workload=name, chose=loop.describe(), host_plan_s=host_plan_s)
    setup_s = time.perf_counter() - t0

    trace_dir = OUT / "trace" / name
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0       # no event per Python call
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    c0 = compiles.count
    counters: dict = {}
    ends = []                     # the host clock at the end of each unit
    start = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
        while True:
            _watch_for_stall(ends)
            for k, v in loop.step().items():
                counters[k] = counters.get(k, 0) + v
            ends.append(time.perf_counter() - start)
            window_s = ends[-1]
            if window_s >= seconds:
                break
    faulthandler.cancel_dump_traceback_later()
    if traced:
        jax.profiler.stop_trace()
    in_window = compiles.count - c0
    info(counters=counters, window_s=window_s, compiles_in_window=in_window,
         unit_s=[b - a for a, b in zip([0.0] + ends, ends)])
    peak = memory_peak(devices)
    work = loop.work(counters)
    counters.update(loop.counters())

    loop.finish()                 # the program's own result assembly
    gc.collect()                  # its state freed before the reference
    numbers = loop.check()
    checks = _checks(numbers, limits)

    run = Run(len(devices), devices[0].device_kind, setup_s, window_s,
              counters, work, host_plan_s)
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices),
           "memory_peak_bytes": peak}
    result = {"correct": _correct(checks),
              "attempted": counters.get(loop.UNIT, 0), "failed": 0}
    if traced:
        run.trace = trace_mod.reduce(trace_mod.find(trace_dir),
                                     len(devices))
        dev.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        result["breakdown"] = run.trace.breakdown()
    metrics = {}
    for m in layout.metrics(name, traced):
        value = layout.module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=dev)
    if "breakdown" in result:
        result["breakdown"] = result.pop("breakdown")
    result["checks"] = checks
    for k, c in checks.items():
        print(f"bench: check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result
