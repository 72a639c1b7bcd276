"""``chip_smoke.py``'s phases at ``HeatConfig().smoke()`` size on the CPU.

The smoke script is the proof that the main path runs on a TPU; these tests
run its phase functions here (Pallas in interpret mode) so the script keeps
working between chip runs.  The device phase must refuse the CPU.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from conftest import ftcs_oracle

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402
from repro.configs.heat3d import HeatConfig, make_field  # noqa: E402

SMOKE = HeatConfig().smoke()


def test_device_phase_refuses_cpu():
    with pytest.raises(cs.PhaseFailed, match="no TPU"):
        cs.phase_device()


def test_main_fails_without_tpu(capsys):
    with pytest.raises(cs.PhaseFailed):
        cs.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_heat_reference_matches_numpy_oracle():
    T0 = make_field(SMOKE)
    ref = cs.reference_heat(T0, SMOKE.omega, 8)
    np.testing.assert_allclose(ref, ftcs_oracle(T0, SMOKE.omega, 8),
                               rtol=1e-6)


def test_explicit_phase_at_smoke_size():
    out = cs.phase_explicit(SMOKE)
    assert out["rel_err"] <= cs.RTOL
    assert out["segment"] == "fused" and out["fallbacks"] == 0
    # k=4 at 64 steps on a 16x16 brick (4·k·h ≤ 16), halo-resident here
    assert out["time_tile"] == 4 and out["resident_layout"]
    assert out["resident_dropped"] == 0


def test_solve_phase_at_smoke_size():
    out = cs.phase_solve(SMOKE)
    assert out["outcome"] == "CONVERGED"
    assert out["true_rel_residual"] <= cs.SOLVE_RTOL
    assert 1 < out["iterations"] < SMOKE.maxiter


def test_btcs_residual_sees_an_unsolved_field():
    T0 = make_field(SMOKE)
    assert cs.btcs_residual(T0, T0, SMOKE.omega) > 1e-3


def test_service_phase_at_smoke_size():
    out = cs.phase_service(SMOKE)
    assert out["requests"] == 5 and out["rel_err"] <= cs.RTOL
    assert out["retries"] == out["degraded"] == 0
    assert out["kernels_built_after_warmup"] == 0


def test_mesh_phase_on_four_cpu_devices():
    """``--chips 4``'s comparison on four virtual CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(ROOT / "src")
    code = (
        "import json, jax, chip_smoke as cs\n"
        "from repro.configs.heat3d import HeatConfig\n"
        "print(json.dumps(cs.phase_mesh(HeatConfig().smoke(), jax.devices())))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    assert '"solve_rel_err"' in out.stdout
