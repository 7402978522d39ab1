"""CPU tests of ``bench/spans.py``: the interval arithmetic of nested
spans, and recorded chip traces with the program's spans reduced to
fixed numbers by the metric readers.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spans, trace  # noqa: E402

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
with open(TESTDATA / "spans.json") as f:
    RECORDED = json.load(f)


def test_nested_spans_and_idle_split():
    """One device, window [0, 100) ns: the benchmark's spans hold the
    program's, a span on a second thread nests in nothing there, and a
    span runs past the window's end."""
    main, other = ("/host:CPU", 0), ("/host:CPU", 1)
    got = spans.reduce_spans([
        ("bench.window", 0, 100, main, 0),
        ("bench.update", 0, 10, main, 0),
        ("bench.fusedmm", 10, 90, main, 0),
        ("api.fusedmm", 12, 88, main, 0),
        ("api.put", 14, 30, main, 0),
        ("api.upload", 15, 20, main, 100),
        ("api.upload", 22, 28, main, 200),
        ("api.upload", 31, 33, other, 50),
        ("api.assemble", 40, 88, main, 0),
        ("api.wait", 41, 60, main, 0),
        ("api.fetch", 60, 70, main, 400),
        ("bench.fusedmm", 95, 110, main, 0),
    ], 0, 100, [[(5, 16), (50, 65), (90, 97)]], 1)
    ns = 1e-9
    assert got.window_s == pytest.approx(100 * ns)
    assert got.inclusive == pytest.approx({
        "bench.update": 10 * ns, "bench.fusedmm": 85 * ns,
        "api.fusedmm": 76 * ns, "api.put": 16 * ns, "api.upload": 13 * ns,
        "api.assemble": 48 * ns, "api.wait": 19 * ns, "api.fetch": 10 * ns})
    # self: less the spans nested on the same thread
    assert got.self_s == pytest.approx({
        "bench.update": 10 * ns, "bench.fusedmm": 9 * ns,
        "api.fusedmm": 12 * ns, "api.put": 5 * ns, "api.upload": 13 * ns,
        "api.assemble": 19 * ns, "api.wait": 19 * ns, "api.fetch": 10 * ns})
    assert got.count["api.upload"] == 3 and got.count["bench.fusedmm"] == 2
    assert got.bytes == {"api.upload": 350, "api.fetch": 400}
    assert got.idle == pytest.approx({
        "bench.update": 5 * ns, "bench.fusedmm": 4 * ns,
        "api.fusedmm": 2 * ns, "api.put": 1 * ns, "api.upload": 1 * ns,
        "api.wait": 10 * ns, "api.fetch": 5 * ns, spans.OUTSIDE: 5 * ns})
    assert sum(got.idle.values()) == pytest.approx(33 * ns, rel=1e-12)


@pytest.fixture
def recorded(monkeypatch):
    """The readers look for a run's trace among the recorded ones, which
    sit beside an older trace with another window."""
    monkeypatch.setattr(spans, "TRACES", TESTDATA)
    assert len(list(TESTDATA.glob("*.xplane.pb"))) > len(RECORDED["traces"])
    return harness.Layout.load()


@pytest.mark.parametrize("rec", RECORDED["traces"],
                         ids=[r["workload"] for r in RECORDED["traces"]])
def test_recorded_trace_reduces_to_fixed_numbers(recorded, rec):
    path = str(TESTDATA / rec["file"])
    red = trace.reduce(path, rec["chips"])
    run = harness.Run(rec["chips"], "TPU v5 lite", 0.0, red.window_s,
                      rec["counters"], {}, trace=red)
    assert spans.find(red.window_s) == path
    got = spans.of(run)
    idle_s = red.window_s - red.busy_s
    assert sum(got.idle.values()) == pytest.approx(idle_s, rel=1e-9)
    assert got.idle == pytest.approx(rec["idle"], rel=1e-9)
    for name in RECORDED["metrics"]:
        value = recorded.module("metrics", name).read(run)
        want = rec["metrics"].get(name)
        if want is None:
            assert value is None, name
        else:
            assert want > 0 and value == pytest.approx(want, rel=1e-9), name


def test_untraced_run_reads_nothing():
    run = harness.Run(1, "TPU v5 lite", 0.0, 1.0, {"rounds": 1}, {})
    assert spans.of(run) is None
    assert spans.per_unit_ms(run, "api.put", "inclusive", "rounds") is None
