"""The program's own host spans in a run's profiler trace, reduced to
per-layer numbers.

The program opens host spans on the profiler's clock (``repro.obs.span``:
``api.*`` in the api layer, ``als.*`` in the apps); the harness opens its
own (``bench.*``).  From the run's ``.xplane.pb``, inside the harness's
``bench.window``, for each span name:

* inclusive seconds: the union of the name's intervals;
* self seconds: each span's interval less what the spans nested in it
  cover, summed;
* how many spans the window opened, and the sum of their ``bytes``.

And the device's idle time split by what the host was doing: each idle
interval of each device, cut at span boundaries, goes piece by piece to
the innermost program span open at that moment; where none is open, to
the innermost benchmark span other than the window; else to
``OUTSIDE``.  Pieces are means over the devices, as ``trace.reduce``'s
idle time is, and sum to it.

A run's trace is found among the traces under ``TRACES`` as the one
whose ``bench.window`` lasts exactly the run's traced window, so a trace
left there by another cell is never read.  Each trace is parsed once per
process, and the first reduction of a run's trace is printed through
``harness.info``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

from bench import harness, readers, trace

TRACES = harness.OUT / "trace"
PROGRAM = ("api.", "als.")
BENCH = "bench."
OUTSIDE = "outside any span"

_PARSED: dict = {}               # path -> (ProfileData, its host spans)
_REDUCED: dict = {}              # (path, chips) -> Spans


@dataclasses.dataclass
class Spans:
    window_s: float
    inclusive: dict      # span name -> seconds
    self_s: dict         # span name -> seconds
    count: dict          # span name -> spans opened in the window
    bytes: dict          # span name -> sum of their ``bytes`` stats
    idle: dict           # label -> idle seconds, mean over devices

    def rates(self) -> dict:
        """Bytes per second of the spans that carry ``bytes``, over their
        inclusive time, in GB/s."""
        return {k: v / self.inclusive[k] / 1e9 for k, v in self.bytes.items()
                if self.inclusive.get(k)}


def _parse(path: str):
    """The trace at ``path`` and its host spans (``_host_spans``)."""
    if path not in _PARSED:
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        _PARSED[path] = data, _host_spans(data)
    return _PARSED[path]


def _host_spans(data):
    """(name, start, end, line, bytes) of every ``api.*``, ``als.*`` and
    ``bench.*`` event on the host planes, in ns."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PROGRAM + (BENCH,)):
                    stats = dict(e.stats)
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                (plane.name, i), stats.get("bytes", 0)))
    return out


def _window(spans):
    window = [(s, e) for n, s, e, *_ in spans if n == trace.WINDOW]
    if len(window) != 1:
        raise ValueError(f"{len(window)} {trace.WINDOW} spans")
    return window[0]


def _self_seconds(spans, lo, hi) -> dict:
    """Span name -> its spans' intervals less their nested spans', clipped
    to [lo, hi), in seconds.  Nesting is containment on one thread."""
    children = collections.defaultdict(list)
    by_line = collections.defaultdict(list)
    for k, sp in enumerate(spans):
        by_line[sp[3]].append(k)
    for ks in by_line.values():
        stack = []
        for k in sorted(ks, key=lambda k: (spans[k][1], -spans[k][2])):
            while stack and spans[stack[-1]][2] < spans[k][2]:
                stack.pop()
            if stack:
                children[stack[-1]].append(k)
            stack.append(k)
    out = collections.Counter()
    for k, (name, s, e, *_) in enumerate(spans):
        own = trace.clip([(s, e)], lo, hi)
        inner = trace.union(trace.clip(
            [spans[c][1:3] for c in children[k]], lo, hi))
        out[name] += trace.total(trace.subtract(own, inner)) / 1e9
    return dict(out)


def _segments(spans, lo, hi):
    """[lo, hi) cut at every span boundary: (cuts, label of each piece)."""
    cuts = sorted({lo, hi} | {t for _, s, e, *_ in spans for t in (s, e)
                              if lo < t < hi})
    by_start = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    opened, i, labels = [], 0, []
    for a in cuts[:-1]:
        opened = [sp for sp in opened if sp[2] > a]
        while i < len(by_start) and by_start[i][1] <= a:
            if by_start[i][2] > a:
                opened.append(by_start[i])
            i += 1
        labels.append(_innermost(opened))
    return cuts, labels


def _innermost(opened) -> str:
    """The program span opened last, else the benchmark span opened
    last, else ``OUTSIDE``; ``opened`` is in order of opening."""
    for prefixes in (PROGRAM, (BENCH,)):
        for name, *_ in reversed(opened):
            if name.startswith(prefixes):
                return name
    return OUTSIDE


def _device_idle(data, chips: int, lo, hi):
    """Each of the first ``chips`` devices' idle intervals in [lo, hi),
    as ``trace.reduce`` finds them."""
    tpus = sorted((p for p in data.planes
                   if p.name.startswith("/device:TPU:")
                   and not p.name.endswith("SparseCore")),
                  key=lambda p: p.name)[:chips]
    if len(tpus) < chips:
        raise ValueError(f"{len(tpus)} device planes for {chips} chips")
    for plane in tpus:
        ops = [(e.start_ns, e.start_ns + e.duration_ns)
               for line in plane.lines if line.name == trace.OPS_LINE
               for e in line.events]
        yield trace.subtract([(lo, hi)], trace.union(trace.clip(ops, lo,
                                                                hi)))


def reduce(path: str, chips: int) -> Spans:
    """The trace at ``path`` reduced over its first ``chips`` devices."""
    data, spans = _parse(path)
    lo, hi = _window(spans)
    return reduce_spans(spans, lo, hi, _device_idle(data, chips, lo, hi),
                        chips)


def reduce_spans(spans, lo, hi, idle, chips: int) -> Spans:
    """The numbers of ``spans`` ((name, start, end, line, bytes) in ns)
    inside the window [lo, hi), with ``idle`` the idle intervals of each
    of ``chips`` devices."""
    spans = [sp for sp in spans if sp[0] != trace.WINDOW
             and sp[2] > lo and sp[1] < hi]
    inclusive = {name: trace.total(trace.union(trace.clip(
        [sp[1:3] for sp in spans if sp[0] == name], lo, hi))) / 1e9
        for name in {sp[0] for sp in spans}}
    count, nbytes = collections.Counter(), collections.Counter()
    for name, s, _, _, b in spans:
        if s >= lo:
            count[name] += 1
            if b:
                nbytes[name] += b
    cuts, labels = _segments(spans, lo, hi)
    split = collections.Counter()
    for gaps in idle:
        for s, e in gaps:
            j = bisect.bisect_right(cuts, s) - 1
            while j < len(labels) and cuts[j] < e:
                split[labels[j]] += (min(e, cuts[j + 1]) - max(s, cuts[j])) \
                    / 1e9 / chips
                j += 1
    return Spans((hi - lo) / 1e9, inclusive, _self_seconds(spans, lo, hi),
                 dict(count), dict(nbytes), dict(split))


def find(window_s: float) -> str:
    """The trace under ``TRACES`` whose window lasts ``window_s``."""
    paths = glob.glob(os.path.join(str(TRACES), "**", "*.xplane.pb"),
                      recursive=True)
    found = []
    for path in paths:
        try:
            lo, hi = _window(_parse(path)[1])
        except ValueError:
            continue
        if (hi - lo) / 1e9 == window_s:
            found.append(path)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} traces under {TRACES} with "
                                f"a {window_s} s window")
    return found[0]


def of(run: harness.Run):
    """The spans of the run's own trace; None in an untraced run."""
    if run.trace is None:
        return None
    key = (find(run.trace.window_s), run.chips)
    if key not in _REDUCED:
        got = _REDUCED[key] = reduce(*key)
        harness.info(idle_split=dict(sorted(got.idle.items(),
                                            key=lambda kv: -kv[1])),
                     idle_s=sum(got.idle.values()),
                     spans={k: {"inclusive_s": got.inclusive[k],
                                "self_s": got.self_s[k],
                                "count": got.count.get(k, 0)}
                            for k in sorted(got.inclusive)},
                     gb_per_s=got.rates())
    return _REDUCED[key]


def per_unit_ms(run: harness.Run, name: str, measure: str, unit: str):
    """The window's ``measure`` (``inclusive`` or ``self_s``) seconds of
    the span ``name`` per completed ``unit``, in ms; None where the run
    has no trace or the trace no such span."""
    got = of(run)
    if got is None or name not in got.inclusive:
        return None
    return readers.per_unit(run, getattr(got, measure)[name], unit, 1e3)
