"""Halo-resident field state: no-copy guarantees + bitwise exactness.

The residency PR's acceptance surface: the layout's enter/exit conversions
round-trip exactly, the in-place wrap refresh reproduces ``jnp.pad(
mode="wrap")`` bitwise, resident stepping equals the legacy repacking path
bit-for-bit (fp32 in-process; fp64 and the sharded mesh in subprocesses,
for heat3d and the off-axis advection–diffusion body), the jitted executors
really donate their entry buffers (buffer invalidation where the backend
effects donation, compiled-HLO donation markers regardless), and the engine
accounting shows two repacking conversions per resident run instead of one
per launch.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import heat_init
from repro.core import WSE_Array, WSE_For_Loop, WSE_Interface
from repro.engine import HaloLayout, plan, reset_stats, single_runner, stats
from repro.engine.layout import wrap_refresh, wrap_refresh_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build_heat(T0, steps, c=0.1, dtype=None):
    wse = WSE_Interface()
    center = 1.0 - 6.0 * c
    kw = {} if dtype is None else {"dtype": dtype}
    T = WSE_Array("T_n", init_data=T0, **kw)
    with WSE_For_Loop("t", steps):
        T[1:-1, 0, 0] = center * T[1:-1, 0, 0] + c * (
            T[2:, 0, 0]
            + T[:-2, 0, 0]
            + T[1:-1, 1, 0]
            + T[1:-1, 0, -1]
            + T[1:-1, -1, 0]
            + T[1:-1, 0, 1]
        )
    return wse, T


def build_advdiff(T0, steps):
    """Off-axis taps (diagonal cross-diffusion) + upwind advection."""
    wse = WSE_Interface()
    T = WSE_Array("T_adv", init_data=T0)
    with WSE_For_Loop("t", steps):
        T[1:-1, 0, 0] = (
            T[1:-1, 0, 0]
            + 0.05
            * (
                T[2:, 0, 0]
                + T[:-2, 0, 0]
                + T[1:-1, 1, 0]
                + T[1:-1, -1, 0]
                + T[1:-1, 0, 1]
                + T[1:-1, 0, -1]
                - 6.0 * T[1:-1, 0, 0]
            )
            - 0.1 * (T[1:-1, 0, 0] - T[1:-1, -1, 0])
            - 0.07 * (T[1:-1, 0, 0] - T[1:-1, 0, -1])
            + 0.02 * (T[1:-1, 1, 1] + T[1:-1, -1, -1] - 2.0 * T[1:-1, 0, 0])
        )
    return wse, T


def build_wide(T0, steps):
    """Depth-2 taps in X and Y: cells next to the Moat read the wrapped
    margins, so a stale margin changes the answer (the depth-1 bodies'
    margins only ever feed Moat cells, which the mask keeps)."""
    wse = WSE_Interface()
    T = WSE_Array("T_w", init_data=T0)
    with WSE_For_Loop("t", steps):
        T[1:-1, 0, 0] = 0.5 * T[1:-1, 0, 0] + 0.1 * (
            T[1:-1, 0, 2] + T[1:-1, 0, -2] + T[1:-1, 2, 0] + T[1:-1, -2, 0]
        )
    return wse, T


# -- layout primitives --------------------------------------------------------


def test_layout_enter_exit_roundtrip_bitwise(rng):
    env = {
        "a": rng.normal(size=(7, 9, 5)).astype(np.float32),
        "b": rng.normal(size=(7, 9, 4)).astype(np.float32),
    }
    lay = HaloLayout(pad=3, shapes={n: v.shape for n, v in env.items()})
    back = lay.exit(lay.enter(env))
    for n, v in env.items():
        assert np.asarray(back[n]).shape == v.shape
        assert (np.asarray(back[n]) == v).all()
    # pad=0 degrades to identity
    lay0 = HaloLayout(pad=0, shapes={})
    assert (np.asarray(lay0.exit(lay0.enter(env))["a"]) == env["a"]).all()


@pytest.mark.parametrize("rows_only", [False, True])
@pytest.mark.parametrize("K, h", [(1, 1), (3, 2), (3, 3)])
def test_wrap_refresh_matches_jnp_pad_wrap(rng, K, h, rows_only):
    """The four-slab refresh rebuilds the whole depth-h wrap frame; the
    rows-only refresh (one device, whose kernel wraps Y itself) the rows
    above and below the brick, over the interior columns."""
    x = rng.normal(size=(8, 6, 4)).astype(np.float32)
    lay = HaloLayout(pad=K, shapes={"x": x.shape})
    refresh = wrap_refresh_rows if rows_only else wrap_refresh
    resident = refresh(lay.enter({"x": x})["x"], K, h)
    ref = jnp.pad(jnp.asarray(x), ((h, h), (h, h), (0, 0)), mode="wrap")
    lo = K - h
    window = resident[lo : lo + 8 + 2 * h, lo : lo + 6 + 2 * h, :]
    if rows_only:
        window, ref = window[:, h : h + 6], ref[:, h : h + 6]
    assert (np.asarray(window) == np.asarray(ref)).all()


# -- resident stepping == repacking stepping (fp32, in-process) ---------------
# Resident launches double-buffer and run in pairs per loop iteration, an odd
# one after the loop (executor.run_launches): even and odd launch counts and
# the n % k remainder take different paths through it.


@pytest.mark.parametrize("steps", [6, 5])  # even, odd launch count
def test_resident_matches_repack_bitwise_heat(steps):
    T0 = heat_init()
    wse, T = build_heat(T0, steps)
    res = wse.make(answer=T, backend="pallas").copy()
    wse, T = build_heat(T0, steps)
    leg = wse.make(answer=T, backend="pallas", resident=False).copy()
    assert (res == leg).all()


@pytest.mark.parametrize("steps", [5, 6])  # odd, even launch count
def test_resident_matches_repack_bitwise_advdiff(steps):
    rng = np.random.default_rng(3)
    T0 = rng.uniform(0.0, 1.0, size=(10, 9, 6)).astype(np.float32)
    wse, T = build_advdiff(T0, steps)
    res = wse.make(answer=T, backend="pallas").copy()
    wse, T = build_advdiff(T0, steps)
    leg = wse.make(answer=T, backend="pallas", resident=False).copy()
    assert (res == leg).all()


@pytest.mark.parametrize("steps, k", [(5, 1), (6, 2)])
def test_resident_matches_repack_bitwise_wide_halo(steps, k):
    rng = np.random.default_rng(5)
    T0 = rng.uniform(0.0, 1.0, size=(10, 12, 6)).astype(np.float32)
    wse, T = build_wide(T0, steps)
    res = wse.make(answer=T, backend="pallas", time_tile=k).copy()
    wse, T = build_wide(T0, steps)
    leg = wse.make(answer=T, backend="pallas", time_tile=k,
                   resident=False).copy()
    assert (res == leg).all()


# (steps, k): tiled launches and remainder launches, each even or odd
@pytest.mark.parametrize("steps, k", [(7, 4), (10, 4), (9, 2)])
def test_resident_matches_repack_bitwise_tiled_remainder(steps, k):
    T0 = heat_init()
    wse, T = build_heat(T0, steps)
    res = wse.make(answer=T, backend="pallas", time_tile=k).copy()
    wse, T = build_heat(T0, steps)
    leg = wse.make(answer=T, backend="pallas", time_tile=k,
                   resident=False).copy()
    assert (res == leg).all()


def _legacy_answer(workload, shape, steps, k):
    """The repacking run of a service workload's program, ``steps`` steps."""
    from repro.engine import RunOptions, run_program
    from repro.service.workloads import get_workload

    program, answer = get_workload(workload).record(shape, np.float32, steps)
    opts = RunOptions(backend="pallas", time_tile=k, resident=False)
    return run_program(program, options=opts)[answer]


@pytest.mark.parametrize("workload", ["heat3d", "advdiff"])
@pytest.mark.parametrize("loop", ["guarded", "service"])
def test_resident_step_loops_match_repack_bitwise(workload, loop):
    """The guarded while-loop (``check_finite``) and the service's chunk
    runner step through the same paired-launch helper as the executor:
    both equal the repacking run bitwise, with odd chunks and a remainder."""
    from repro.engine import RunOptions, run_program
    from repro.service.requests import PlanSignature
    from repro.service.workloads import build_workload, get_workload

    shape, steps, k = (10, 12, 6), 9, 2
    leg = _legacy_answer(workload, shape, steps, k)
    reset_stats()
    if loop == "guarded":
        program, answer = get_workload(workload).record(shape, np.float32,
                                                        steps)
        # chunks of 3 launches (odd, inside the while-loop), then the
        # remainder launch
        opts = RunOptions(backend="pallas", time_tile=k, check_finite=6)
        res = run_program(program, options=opts)[answer]
        assert stats.resident_runs == 1 and stats.health_probes >= 2
    else:
        cw = build_workload(PlanSignature(workload, shape, time_tile=k))
        assert cw.layout.pad == k
        env = cw.initial_env(None)
        for m in (6, 3):  # 3 tiled launches; 1 tiled + the remainder
            env = cw.advance(m)(env)
        res = cw.finalize(env)
    assert (np.asarray(res) == leg).all()


def test_resident_accounting_two_repacks_per_run():
    T0 = heat_init()
    reset_stats()
    wse, T = build_heat(T0, 6)
    wse.make(answer=T, backend="pallas", time_tile=1)
    assert stats.resident_runs == 1
    assert stats.repacks == 2  # layout enter + exit — not one per launch
    assert stats.exchanges == 6  # margin refreshes, one per launch
    reset_stats()
    wse, T = build_heat(T0, 6)
    wse.make(answer=T, backend="pallas", time_tile=1, resident=False)
    assert stats.resident_runs == 0
    assert stats.repacks == 6  # legacy: one full wrap pad per launch


def test_mixed_plan_counts_conversions_around_interp_segments():
    """fused loop → non-affine loop (interpreter) → fused loop: the resident
    run exits/re-enters the layout around the interpreter segment, and the
    accounting must report all four conversions, not a flat two."""
    T0 = heat_init((8, 8, 6))
    wse = WSE_Interface()
    T = WSE_Array("T_m", init_data=T0)
    with WSE_For_Loop("a", 2):
        T[1:-1, 0, 0] = 0.5 * T[1:-1, 0, 0] + 0.1 * T[1:-1, 1, 0]
    with WSE_For_Loop("b", 2):
        T[1:-1, 0, 0] = T[1:-1, 0, 0] * T[1:-1, 0, 0] * T[1:-1, 1, 0]
    with WSE_For_Loop("c", 2):
        T[1:-1, 0, 0] = 0.5 * T[1:-1, 0, 0] + 0.1 * T[1:-1, -1, 0]
    reset_stats()
    res = wse.make(answer=T, backend="pallas").copy()
    assert stats.resident_runs == 1
    assert stats.repacks == 4  # enter, exit-around-interp, enter, exit
    wse = WSE_Interface()
    T = WSE_Array("T_m", init_data=T0)
    with WSE_For_Loop("a", 2):
        T[1:-1, 0, 0] = 0.5 * T[1:-1, 0, 0] + 0.1 * T[1:-1, 1, 0]
    with WSE_For_Loop("b", 2):
        T[1:-1, 0, 0] = T[1:-1, 0, 0] * T[1:-1, 0, 0] * T[1:-1, 1, 0]
    with WSE_For_Loop("c", 2):
        T[1:-1, 0, 0] = 0.5 * T[1:-1, 0, 0] + 0.1 * T[1:-1, -1, 0]
    leg = wse.make(answer=T, backend="pallas", resident=False).copy()
    assert (res == leg).all()


def test_plan_layout_margin_is_max_tile_window():
    T0 = np.asarray(heat_init((24, 24, 8)))
    wse, T = build_heat(T0, 8)
    try:
        p = plan(wse.program, backend="pallas", time_tile=4)
    finally:
        wse.__exit__()
    assert p.layout.pad == 4  # k=4, h=1
    wse, T = build_heat(T0, 8)
    try:
        p = plan(wse.program, backend="jit")
    finally:
        wse.__exit__()
    assert p.layout.pad == 0  # interpreter plans never pad


def test_plan_gate_on_mosaic_keeps_single_device_residency(monkeypatch):
    """Planned as for Mosaic (``_interpret`` False; nothing is traced): a
    single-device plan, batched or not, keeps the resident layout and
    drops nothing, but runs no overlap split; a mesh plan still drops the
    layout and counts it; a differentiable plan never asks for it."""
    import repro.kernels.ops as kops
    from repro.core.jaxcompat import make_mesh
    from repro.engine import RunOptions

    monkeypatch.setattr(kops, "_interpret", lambda: False)
    T0 = heat_init((24, 24, 8))
    wse, T = build_heat(T0, 8)
    wse.__exit__()
    reset_stats()
    for opts in (RunOptions(backend="pallas", time_tile=2),
                 RunOptions(backend="pallas", time_tile=2, batch=3),
                 RunOptions(backend="pallas", time_tile=2, overlap=True)):
        p = plan(wse.program, opts)
        assert p.layout.pad == 2, opts
        assert p.segments[0].kind == "fused" and p.segments[0].split == 0
    assert stats.resident_dropped == 0
    p = plan(wse.program, RunOptions(backend="pallas", differentiable=True))
    assert p.layout.pad == 0 and stats.resident_dropped == 0
    mesh = make_mesh((1, 1), ("data", "model"))
    p = plan(wse.program, RunOptions(backend="pallas", mesh=mesh))
    assert p.layout.pad == 0
    assert stats.resident_dropped == 1


# -- donation -----------------------------------------------------------------


def test_single_runner_donates_entry_buffers():
    T0 = heat_init()
    wse, T = build_heat(T0, 4)
    try:
        p = plan(wse.program, backend="pallas")
    finally:
        wse.__exit__()
    runner = single_runner(p)
    env = {"T_n": jnp.asarray(T0)}
    lowered = runner.lower(env).as_text()
    assert "jax.buffer_donor" in lowered or "tf.aliasing_output" in lowered
    out = runner(env)
    jax.block_until_ready(out["T_n"])
    # where the backend effects donation (CPU does), the entry buffer is gone
    if hasattr(env["T_n"], "is_deleted"):
        assert env["T_n"].is_deleted()


def test_solver_step_fn_protects_caller_arrays():
    """make_solver donates its jitted entry state; step_fn must hand it a
    buffer the caller never owned, so reusing one jax array across calls
    stays legal and bitwise stable."""
    from repro.solver import btcs_program, make_solver

    T0 = heat_init((8, 8, 8))
    prog = btcs_program((8, 8, 8), 0.1, init_data=T0)
    step = make_solver(prog, "T", method="cg", backend="jit", tol=1e-6)
    x = jnp.asarray(T0)
    a, _ = step(x)
    b, _ = step(x)  # donated run must not have consumed the caller's x
    assert not x.is_deleted()
    assert (np.asarray(a) == np.asarray(b)).all()


# -- fp64 + sharded exactness (subprocesses) ----------------------------------


def run_py(code: str, devices: int = 1, x64: bool = False, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    if x64:
        env["JAX_ENABLE_X64"] = "1"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


BUILDERS = """
import numpy as np
from repro.core import WSE_Array, WSE_For_Loop, WSE_Interface

def build_heat(T0, steps, c=0.1, dtype=None):
    wse = WSE_Interface()
    center = 1.0 - 6.0 * c
    kw = {} if dtype is None else {"dtype": dtype}
    T = WSE_Array("T_n", init_data=T0, **kw)
    with WSE_For_Loop("t", steps):
        T[1:-1, 0, 0] = center * T[1:-1, 0, 0] + c * (
            T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0]
            + T[1:-1, 0, -1] + T[1:-1, -1, 0] + T[1:-1, 0, 1])
    return wse, T

def build_advdiff(T0, steps, dtype=None):
    wse = WSE_Interface()
    kw = {} if dtype is None else {"dtype": dtype}
    T = WSE_Array("T_adv", init_data=T0, **kw)
    with WSE_For_Loop("t", steps):
        T[1:-1, 0, 0] = (T[1:-1, 0, 0]
            + 0.05 * (T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0]
                      + T[1:-1, -1, 0] + T[1:-1, 0, 1] + T[1:-1, 0, -1]
                      - 6.0 * T[1:-1, 0, 0])
            - 0.1 * (T[1:-1, 0, 0] - T[1:-1, -1, 0])
            - 0.07 * (T[1:-1, 0, 0] - T[1:-1, 0, -1])
            + 0.02 * (T[1:-1, 1, 1] + T[1:-1, -1, -1]
                      - 2.0 * T[1:-1, 0, 0]))
    return wse, T

T0 = np.full((8, 12, 10), 500.0, np.float64)
T0[1:-1, 1:-1, 0] = 300.0
T0[1:-1, 1:-1, -1] = 400.0
rng = np.random.default_rng(3)
A0 = rng.uniform(0.0, 1.0, size=(8, 12, 10))
"""


def test_fp64_resident_bitwise_single_device():
    out = run_py(BUILDERS + """
for builder, T_init in [(build_heat, T0), (build_advdiff, A0)]:
    for steps in (6, 5):  # even, odd launch count
        wse, T = builder(T_init, steps, dtype=np.float64)
        res = wse.make(answer=T, backend="pallas").copy()
        assert res.dtype == np.float64, res.dtype
        wse, T = builder(T_init, steps, dtype=np.float64)
        leg = wse.make(answer=T, backend="pallas", resident=False).copy()
        assert (res == leg).all(), (builder, steps)
for steps in (8, 11):  # no remainder; an odd remainder of 3
    wse, T = build_heat(T0, steps, dtype=np.float64)
    rk = wse.make(answer=T, backend="pallas", time_tile=4).copy()
    wse, T = build_heat(T0, steps, dtype=np.float64)
    lk = wse.make(answer=T, backend="pallas", time_tile=4,
                  resident=False).copy()
    assert (rk == lk).all(), steps
print("OK")
""", x64=True)
    assert "OK" in out


def test_fp64_resident_bitwise_sharded():
    out = run_py(BUILDERS + """
import jax
from repro.core.halo import run_sharded
from repro.core.jaxcompat import make_mesh
from repro.engine import reset_stats, stats
mesh = make_mesh((2, 2), ("data", "model"))
for builder, T_init, name in [(build_heat, T0, "T_n"),
                              (build_advdiff, A0, "T_adv")]:
    wse, T = builder(T_init, 5, dtype=np.float64)
    wse.__exit__()
    reset_stats()
    res = run_sharded(wse.program, {name: T_init}, mesh=mesh,
                      use_pallas=True)[name].copy()
    assert stats.resident_runs == 1 and stats.repacks == 2, vars(stats)
    wse, T = builder(T_init, 5, dtype=np.float64)
    wse.__exit__()
    leg = run_sharded(wse.program, {name: T_init}, mesh=mesh,
                      use_pallas=True, resident=False)[name].copy()
    assert (res == leg).all(), name
    # sharded == single-device, both resident
    wse, T = builder(T_init, 5, dtype=np.float64)
    single = wse.make(answer=T, backend="pallas")
    assert (res == single).all(), name
print("OK")
""", devices=4, x64=True)
    assert "OK" in out
