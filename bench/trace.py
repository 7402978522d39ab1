"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers, with nothing but ``jax.profiler.ProfileData``.

Per device (a ``/device:TPU:<i>`` plane), from its ``XLA Ops`` line:

* busy: the union of the op intervals inside the window;
* kernels: the ops that are Pallas kernels (``KERNEL_TARGET``), summed.

The window is the host span ``bench.window`` that the harness opens
around its timed loop.  Each idle gap of a device inside it goes to the
benchmark's own host span (``bench.*``) that covers most of it, so the
breakdown says what the host was doing while the device waited.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import itertools
import os

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
#: an op of the ops line is named by its HLO text; a Pallas kernel is a
#: custom call to this target
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
IDLE_LABEL = "host outside the benchmark's spans"
TOP = 10


def find(trace_dir) -> str:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {trace_dir}")
    return paths[0]


def union(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(i) for i in out]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(intervals, minus):
    """``intervals`` less the disjoint, sorted ``minus``."""
    out = []
    for s, e in intervals:
        for ms, me in minus:
            if me <= s or ms >= e:
                continue
            if ms > s:
                out.append((s, ms))
            s = max(s, me)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def op_name(text: str) -> str:
    """The instruction's own name, ``%spmm_pallas.1`` of
    ``%spmm_pallas.1 = f32[...] custom-call(...)``."""
    return text.split(" = ", 1)[0]


def is_kernel(text: str) -> bool:
    return KERNEL_TARGET in text


@dataclasses.dataclass
class Device:
    busy_s: float
    kernel_s: float
    ops: dict            # op name -> seconds inside the window


@dataclasses.dataclass
class Reduction:
    window_s: float
    devices: list
    gaps: dict           # host span -> idle seconds, mean over devices

    @property
    def busy_s(self) -> float:
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    @property
    def kernel_s_max(self) -> float:
        return max(d.kernel_s for d in self.devices)

    def breakdown(self) -> dict:
        """The device ops that took most time and the idle time by host
        span, each a mean over the devices, at most ``TOP`` of each."""
        ops = collections.Counter()
        for d in self.devices:
            for k, v in d.ops.items():
                ops[k] += v / len(self.devices)
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops.most_common(TOP)],
                "idle_gaps": [[k, v] for k, v in gaps[:TOP]]}


def _events(plane):
    for line in plane.lines:
        for e in line.events:
            yield line.name, e


def reduce(path: str, chips: int) -> Reduction:
    """The trace at ``path`` reduced over its first ``chips`` devices."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = list(data.planes)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for p in planes if p.name.startswith("/host:")
             for _, e in _events(p) if e.name.startswith("bench.")]
    window = [(s, e) for n, s, e in spans if n == WINDOW]
    if len(window) != 1:
        raise ValueError(f"{len(window)} {WINDOW} spans in {path}")
    lo, hi = window[0]
    spans = sorted((sp for sp in spans if sp[0] != WINDOW),
                   key=lambda sp: sp[1])
    tpus = sorted((p for p in planes if p.name.startswith("/device:TPU:")
                   and not p.name.endswith("SparseCore")),
                  key=lambda p: p.name)[:chips]
    if len(tpus) < chips:
        raise ValueError(f"{len(tpus)} device planes for {chips} chips")
    devices, gaps = [], collections.Counter()
    for plane in tpus:
        ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
               for line, e in _events(plane) if line == OPS_LINE]
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        by_name = collections.Counter()
        for n, s, e in ops:
            by_name[op_name(n)] += total(clip([(s, e)], lo, hi)) / 1e9
        for s, e in subtract([(lo, hi)], busy):
            gaps[_label(spans, s, e)] += (e - s) / 1e9 / chips
        devices.append(Device(
            total(busy) / 1e9,
            total(union(clip([(s, e) for n, s, e in ops if is_kernel(n)],
                             lo, hi))) / 1e9,
            {k: v for k, v in by_name.items() if v > 0}))
    return Reduction((hi - lo) / 1e9, devices, dict(gaps))


def _label(spans, s, e) -> str:
    """The benchmark span that covers most of the gap [s, e); ``spans``
    are sorted by start and do not nest."""
    best, label = 0, IDLE_LABEL
    i = max(bisect.bisect_right(spans, s, key=lambda sp: sp[1]) - 1, 0)
    for name, a, b in itertools.islice(spans, i, None):
        if a >= e:
            break
        cover = min(b, e) - max(a, s)
        if cover > best:
            best, label = cover, name
    return label
