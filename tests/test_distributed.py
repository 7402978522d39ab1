"""Multi-device correctness + comm-cost tests.

XLA fixes the host device count at first backend init, so these run as
subprocesses that force 8 CPU devices before importing jax.  Each script
asserts internally and exits non-zero on failure.
"""
import os
import subprocess
import sys

import pytest

SCRIPTS = os.path.join(os.path.dirname(__file__), "dist_scripts")
SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


def run_script(name):
    env = dict(os.environ)
    # drop any inherited device-count flags (e.g. from importing
    # repro.launch.dryrun in-process) — the scripts set their own
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, name)],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, (
        f"{name} failed:\nstdout:\n{proc.stdout[-4000:]}\n"
        f"stderr:\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.mark.slow
def test_d15_all_modes_all_c():
    out = run_script("check_d15.py")
    assert "ALL D15 OK" in out


@pytest.mark.slow
def test_s15_all_modes_all_c():
    out = run_script("check_s15.py")
    assert "ALL S15 OK" in out


@pytest.mark.slow
def test_d25_all_modes():
    out = run_script("check_d25.py")
    assert "ALL D25 OK" in out


@pytest.mark.slow
def test_s25_all_modes():
    out = run_script("check_s25.py")
    assert "ALL S25 OK" in out


@pytest.mark.slow
def test_d15_overlap_matches_serial_bitwise():
    out = run_script("check_d15_overlap.py")
    assert "D15 OVERLAP IDENTITY OK" in out


@pytest.mark.slow
def test_comm_costs_match_table3():
    out = run_script("check_comm_costs.py")
    assert "ALL COMM COSTS OK" in out


@pytest.mark.slow
def test_comm_sparse_pruned_wire_formats():
    """comm="sparse" bitwise == comm="dense" on every feasible cell,
    measured wire words == the plan-exact pruned-channel model at 1.00x,
    and the power-law problem ships strictly fewer words than the dense
    Table-III optimum."""
    out = run_script("check_comm_sparse.py")
    assert "ALL COMM SPARSE OK" in out
    assert "at 1.00x" in out


@pytest.mark.slow
def test_elastic_remesh_8_to_4():
    out = run_script("check_elastic.py")
    assert "ELASTIC OK" in out


@pytest.mark.slow
def test_elision_cells_match_unfused_sequence():
    """Every registry elision cell vs the unfused sddmm;spmm sequence —
    bitwise for the communication-replaying cells (s15/d25 "fused",
    s25 "reuse", and every "none"), allclose for reassociating ones."""
    out = run_script("check_elision_parity.py")
    assert "ALL ELISION PARITY OK" in out


@pytest.mark.slow
def test_unified_api_cross_algorithm_parity():
    """Every registered algorithm through repro.core.api == kernels/ref,
    plus bitwise-identical Session replication caching."""
    out = run_script("check_api.py")
    assert "ALL API OK" in out


@pytest.mark.slow
def test_distributed_als_and_gat():
    """Paper §VI-E applications end-to-end on the unified API."""
    out = run_script("check_apps_dist.py")
    assert "ALL APPS DIST OK" in out


@pytest.mark.slow
def test_gradients_match_dense_reference():
    """jax.grad through the distributed sddmm/spmm/fusedmm == the dense
    reference on every feasible registry cell (8 devices), Session
    threading bitwise-neutral, trainable apps converge."""
    out = run_script("check_grads.py")
    assert "ALL GRADS OK" in out


@pytest.mark.slow
def test_backward_wire_words_match_extended_model():
    """Measured backward wire words == the impl-exact extended cost
    model at 1.00x per cell, with the Session-replayed backward strictly
    cheaper wherever a dense operand is replicated."""
    out = run_script("check_grad_costs.py")
    assert "ALL GRAD COSTS OK" in out


@pytest.mark.slow
def test_fault_injected_recovery_parity():
    """Every (family x op x elision x session) cell recovers from an
    injected transient fault with bitwise-identical results; seeded
    fault plans replay; a mid-training DeviceLost degrades 8 -> 4 and
    matches a checkpoint-resume onto the same mesh bitwise.  Writes the
    FAULTS_summary.json CI artifact."""
    out = run_script("check_faults.py")
    assert "ALL FAULTS OK" in out
    assert "device-lost re-mesh ok" in out


@pytest.mark.slow
def test_serving_engine_elastic_8dev():
    """Continuous-batching serving engine under seeded traffic on the
    8-device mesh: coalesced ticks bitwise vs solo and vs the numpy
    reference, mid-stream DeviceLost re-meshing the pool's deployments
    (score AND aggregate rounds), pool churn under traffic, and the
    deterministic open-loop latency replay."""
    out = run_script("check_serving.py")
    assert "ALL SERVING OK" in out
    assert "re-meshed to" in out


@pytest.mark.slow
def test_remesh_8_to_4_bitwise():
    """DistProblem.replan / api.degrade shrink 8 -> 4 mid-run with
    bitwise-identical kernel results (integer-exact data); non-divisible
    device counts fail with the constraint trail."""
    out = run_script("check_remesh.py")
    assert "ALL REMESH OK" in out


@pytest.mark.slow
def test_static_schedule_conformance_8dev():
    """Every registry cell's lowered HLO collective sequence matches its
    published schedule (kind, order, replica groups) with the SPMD
    rendezvous simulation deadlock-free; corrupted event lists and
    per-rank programs are caught.  Writes ANALYSIS_report.json."""
    out = run_script("check_analysis.py")
    assert "ALL ANALYSIS OK" in out
    assert "all pass" in out


@pytest.mark.slow
def test_obs_traced_smoke_8dev():
    """Traced 8-device smoke across all four families: every dense
    round's measured/modeled wire-word ratio inside [0.99, 1.01] (the
    impl-exact model lands at 1.0000), per-event word sums equal the
    round model, traced results bitwise vs untraced, and the
    METRICS_smoke.json CI artifact written."""
    out = run_script("check_obs.py")
    assert "ALL OBS OK" in out
    assert "drift=1.0000" in out
