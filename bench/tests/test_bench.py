"""CPU tests of the benchmark: the harness on tiny copies of its cells
(kernels in interpret mode), the work counts, the trace reduction of a
recorded chip trace, the control, and the faults the check must catch.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import peaks, readings, trace, work  # noqa: E402
from bench.tests import tiny  # noqa: E402

R18 = "tiny-rmat18-fusedmm-1chip"
ALS = "tiny-als-er17-cg-1chip"
TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    return tiny.layout(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def cpu():
    return jax.devices()[:1]


# -- work ---------------------------------------------------------------------

def test_work_counts_by_hand():
    rows, cols = np.array([0, 0, 1]), np.array([0, 2, 2])
    w = work.count("fusedmm", rows, cols, 3, 3, 2)
    # 4 nnz r; 3 x (two int32 indices + one value) + 2 distinct rows of X
    # and 2 of Y at r words + the (3, 2) output and the 3 sampled values
    assert w == work.Work(24.0, 36.0 + 32.0 + 36.0)
    assert work.count("sddmm", rows, cols, 3, 3, 2) == work.Work(
        12.0, 36.0 + 32.0 + 12.0)
    assert work.count("spmm", rows, cols, 3, 3, 2) == work.Work(
        12.0, 36.0 + 16.0 + 24.0)


@pytest.mark.parametrize("workload", [R18, ALS])
def test_work_ignores_family_and_packing(layout, cpu, workload):
    """The loop's work for a window reads the COO alone: two families
    and two ``nz_block`` sizes pack the matrix differently and elide
    differently, and give the same operations and bytes."""
    cell = layout.workload(workload)
    traffic = layout.json("traffic", cell["traffic"])
    mod = layout.module("loops", traffic["loop"])
    counts = []
    for family, nz_block in (("d15", 32), ("s15", 128)):
        config = dict(layout.json("configs", cell["config"]),
                      algorithm=family, nz_block=nz_block)
        loop = mod.Loop(config, traffic, cpu, 3)
        loop.plan()
        counts.append(loop.work({"rounds": 3, "half_rounds": 3}))
    assert counts[0] == counts[1]
    assert counts[0]["flops"] > 0 and counts[0]["bytes"] > 0


def test_unknown_device_has_no_peak():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("cpu")


# -- the harness --------------------------------------------------------------

@pytest.mark.parametrize("workload", [R18, ALS])
def test_traffic_loop_runs_through_the_harness(layout, cpu, workload):
    result = tiny.run(layout, workload, cpu)
    assert result["correct"] is True, result
    assert result["attempted"] >= 1 and result["failed"] == 0
    want = {m["name"] for m in layout.metrics(workload, traced=False)}
    assert set(result["metrics"]) == want
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(
        layout.json("cells", workload)["limits"])


def test_parts_dropped_in_are_found_by_name(layout, cpu):
    """A new cell, configuration and metric, as new files in a directory
    of their own plus entries in the benchmark's spec, run with no edit
    to the harness."""
    assert layout.find("configs", "tiny-graph500-rmat18-r128", ".json") \
        .is_relative_to(layout.roots[0])
    result = tiny.run(layout, R18, cpu, seconds=0.2)
    assert result["metrics"][tiny.NEW_METRIC]["value"] == \
        result["attempted"] > 1
    with pytest.raises(LookupError):
        layout.find("metrics", "no-such-metric", ".py")


def test_command_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "rmat18-fusedmm-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU" in proc.stderr


# -- the trace ----------------------------------------------------------------

def test_interval_arithmetic():
    assert trace.union([(3, 5), (0, 2), (1, 4)]) == [(0, 5)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert trace.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def test_recorded_trace_reduces_to_fixed_numbers():
    with open(TESTDATA / "trace.json") as f:
        want = json.load(f)
    got = trace.reduce(str(TESTDATA / want["file"]), want["chips"])
    assert got.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert got.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert got.kernel_s_max == pytest.approx(want["kernel_s_max"], rel=1e-9)
    assert got.breakdown() == want["breakdown"]


# -- correctness: the control and the faults ----------------------------------

@pytest.mark.parametrize("workload", [R18, ALS])
def test_control_fails_the_limits(layout, cpu, workload):
    """The reference at the next precision below, in the program's place,
    fails at least one of the cell's limits; the program passes them."""
    limits = layout.json("cells", workload)["limits"]
    got = readings.readings(layout, workload, 5, 0.0, cpu)
    assert all(v <= limits[k] for k, v in got["program"].items()), got
    assert any(v > limits[k] for k, v in got["control"].items()), got


@pytest.fixture
def fresh_programs():
    """Programs compiled before a fault is planted must not serve the
    run with the fault, nor those compiled with it the next test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _altered(monkeypatch):
    """One answer altered where it is produced: the first output row of
    every SpMM kernel call."""
    from repro.kernels import ops
    spmm = ops.spmm
    monkeypatch.setattr(ops, "spmm",
                        lambda *a, **k: spmm(*a, **k).at[0, 0].add(1.0))


def _half_left_out(monkeypatch):
    """Half of the nonzero blocks left out of every SpMM kernel call."""
    from repro.kernels import ops
    spmm = ops.spmm

    def half(S, *a, **k):
        keep = np.arange(S.nblocks) < S.nblocks // 2
        return spmm(S.with_vals(S.vals * keep[:, None]), *a, **k)
    monkeypatch.setattr(ops, "spmm", half)


def _state_unchanged(monkeypatch):
    """Every FusedMM call returns what the first one returned."""
    from repro.core import api
    fusedmm, first = api.DistProblem.fusedmm, []

    def stale(self, *a, **k):
        if not first:
            first.append(fusedmm(self, *a, **k))
        return first[0]
    monkeypatch.setattr(api.DistProblem, "fusedmm", stale)


def _cg_step_off(monkeypatch):
    """A wrong CG step length: every ``p . Ap`` of ``dist_cg_solve`` a
    tenth too large, so each alpha is a tenth too small."""
    from repro.apps import als
    row_dots = als._row_dots
    monkeypatch.setattr(
        als, "_row_dots",
        lambda X, Y: row_dots(X, Y) * (1.0 if X is Y else 1.1))


FAULTS = {"altered": _altered, "half_left_out": _half_left_out,
          "state_unchanged": _state_unchanged, "cg_step_off": _cg_step_off}
CASES = [(w, f) for w in (R18, ALS) for f in sorted(FAULTS)
         if (w, f) != (R18, "cg_step_off")]


@pytest.mark.parametrize("workload, fault", CASES)
def test_fault_makes_correct_false(layout, cpu, monkeypatch, fresh_programs,
                                   workload, fault):
    FAULTS[fault](monkeypatch)
    assert tiny.run(layout, workload, cpu)["correct"] is False


FOUR_DEVICES = """
import sys, tempfile
sys.path[:0] = {paths!r}
import jax
from bench.tests import tiny
from repro.core import s15
with tempfile.TemporaryDirectory() as d:
    lay = tiny.layout(d)
    sound = tiny.run(lay, {cell!r}, jax.devices()[:4])["correct"]
    s15._shift = lambda x, axis_name, size: x     # no exchange
    jax.clear_caches()
    broken = tiny.run(lay, {cell!r}, jax.devices()[:4])["correct"]
print("CORRECT", sound, broken)
"""


def test_exchange_left_out_makes_correct_false():
    """The four-chip cell on four CPU devices: sound, then with the shifts
    between devices left out."""
    code = FOUR_DEVICES.format(paths=[str(ROOT), str(ROOT / "src")],
                               cell=tiny.FOUR)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.split()[-3:] == ["CORRECT", "True", "False"]

