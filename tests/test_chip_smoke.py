"""CPU rehearsal of chip_smoke.py: every phase at a tiny size, kernels in
interpret mode, plus the script's refusal to run without a TPU."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402

SCALE = 8          # 256-vertex R-MAT graph
R = 128


@pytest.fixture(scope="module")
def devices():
    return jax.devices()[:1]


@pytest.fixture(scope="module")
def graph():
    return cs.rmat_graph(SCALE)


def _check(rec):
    assert rec["ok"], rec
    assert rec["tpu_custom_call"] is False      # interpret mode on CPU


def test_device_check_refuses_cpu():
    with pytest.raises(SystemExit) as e:
        cs.require_tpu(1)
    assert e.value.code not in (0, None)


def test_script_exits_before_any_phase_without_tpu():
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""            # no phase, no result line
    assert "needs 1 TPU" in proc.stderr


def test_measured_reports_the_phase_own_peaks(devices, capsys):
    def phase():
        x = np.ones(1 << 24, np.uint8)         # 16 MiB held for a while
        time.sleep(0.3)
        return dict(ok=bool(x.all()))

    base = cs._host_rss()
    rec = cs.measured("probe", devices, phase)
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == rec
    assert rec["host_peak_bytes"] >= base + (1 << 23)
    assert len(rec["device_peak_bytes"]) == len(devices)
    assert rec["process_peak_bytes"]["host"] > 0


def test_phase_kernels(devices, graph):
    prob, _ = cs.plan(graph, R, devices)
    _check(cs.phase_kernels(prob))


def test_phase_serving(devices, graph):
    rec = cs.phase_serving(graph, R, devices, n_scores=3, pairs=16)
    _check(rec)
    assert rec["completed"] and rec["elastic_recoveries"] == 0


def test_phase_als(devices):
    rec = cs.phase_als(SCALE, R, devices)
    _check(rec)
    assert rec["loss"][1] < rec["loss"][0]


def test_phase_dist_fusedmm(devices, graph):
    rec = cs.phase_dist_fusedmm(graph, R, devices)
    assert rec["ok"], rec
    assert {c["family"] for c in rec["cells"]} >= {"d15", "s15"}
    assert all(c["tpu_custom_call"] is False for c in rec["cells"])


def test_compile_cache_lands_in_the_named_dir(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins; without it the fixed checkout
    path is configured."""
    code = ("import json, jax, jax.numpy as jnp; "
            "from repro.launch import compile_cache; "
            "path = compile_cache.enable(); "
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready(); "
            "print(json.dumps([path, "
            "jax.config.jax_compilation_cache_dir]))")
    base = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=str(ROOT / "src"),
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    base.pop("JAX_COMPILATION_CACHE_DIR", None)
    named = tmp_path / "cache"
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(base, JAX_COMPILATION_CACHE_DIR=str(named)),
        check=True, timeout=300).stdout
    assert json.loads(out.splitlines()[-1]) == [str(named)] * 2
    assert any(named.iterdir())                 # entries landed there
    out = subprocess.run([sys.executable, "-c",
                          code.replace("jax.jit(lambda x: x * 2 + 1)"
                                       "(jnp.ones(8)).block_until_ready"
                                       "(); ", "")],
                         capture_output=True, text=True, env=base,
                         check=True, timeout=300).stdout
    assert json.loads(out.splitlines()[-1]) == [str(ROOT / ".jax_cache")] * 2
