"""Differentiable WFA: adjoint solves + checkpointed reverse stepping.

The acceptance surface of the adjoint PR:

* ``transpose_taps`` is an involution on lowered operators, maps symmetric
  tap sets to themselves (``==`` — same kernel-cache key), and refuses
  nonlinear bodies;
* ``jax.grad`` through ``make_differentiable_solver`` matches central
  finite differences at fp64 for every adjoint method (CG / PipeCG /
  BiCGSTAB / mg / mg-preconditioned CG), with **zero new kernels** built
  during the backward pass for symmetric operators (the adjoint solve hits
  the forward kernel's cache entry) and zero interpreter fallbacks;
* non-affine operator bodies raise a clear ``ValueError`` under the
  differentiable path instead of silently falling back;
* the checkpointed reverse stepper (``differentiable_runner`` /
  ``ftcs_solve_checkpointed``) reproduces the non-checkpointed gradient to
  ~ulp across time-tile factors and remainder steps (hypothesis property +
  fixed cases);
* under AD the jitted runners stop donating (no donation markers in the
  HLO, caller arrays stay alive), and the sharded-mesh gradient matches
  single-device to a few ulps (fp64 subprocesses, as in test_residency).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import heat_init
from gradcheck import assert_gradcheck, gradcheck, probe_points
from repro.compiler import (
    LoweringError,
    Tap,
    lower_group,
    transpose_taps,
)
from repro.core import WSE_Array, WSE_For_Loop, WSE_Interface
from repro.core.explicit import ftcs_solve, ftcs_solve_checkpointed
from repro.core.field import Field
from repro.core.program import ForLoop, scoped_program
from repro.engine import RunOptions, differentiable_runner, plan, single_runner
from repro.solver import ADJOINT_METHODS, make_differentiable_solver, make_solver
from repro.solver.api import _answer_name, _lower_operator, _split
from repro.solver.presets import btcs_program, poisson_program

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def _lowered(program, answer="T"):
    name = _answer_name(program, answer)
    (_, op_ops), _ = _split(program, name)
    return _lower_operator(op_ops, name), name


# -- transpose_taps -----------------------------------------------------------


def test_transpose_taps_symmetric_fixed_point():
    """A symmetric operator's transpose is the *same* LoweredGroup — the
    equality the kernel cache keys on."""
    group, name = _lowered(btcs_program((8, 8, 6), 0.2))
    t = transpose_taps(group, name)
    assert t == group


def test_transpose_taps_involution_nonsymmetric():
    """transpose ∘ transpose == identity on an asymmetric tap set."""
    wse = WSE_Interface()
    T = WSE_Array("T", shape=(8, 8, 6))
    with WSE_For_Loop("t", 1):
        T[1:-1, 0, 0] = (
            T[1:-1, 0, 0]
            - 0.1 * (T[1:-1, 0, 0] - T[1:-1, -1, 0])
            + 0.05 * (T[2:, 1, 1] - T[1:-1, 0, 0])
        )
    ops = list(wse.program.ops)
    wse.__exit__()
    group, name = lower_group(ops), "T"
    t = transpose_taps(group, name)
    assert t != group
    assert transpose_taps(t, name) == group
    # the answer taps are mirrored, coefficient-free here
    fwd = sorted(tap for u in group.updates for _, taps in u.terms for tap in taps)
    bwd = sorted(
        Tap(tap.field, -tap.dz, -tap.dx, -tap.dy)
        for u in t.updates
        for _, taps in u.terms
        for tap in taps
    )
    assert fwd == bwd


def test_transpose_taps_shifts_coefficient_taps():
    """c·C[p]·x[p+o] transposes to c·C[p−o]·x[p−o] (coefficient taps move
    by −o_x relative to the row)."""
    wse = WSE_Interface()
    T = WSE_Array("T", shape=(8, 8, 6))
    C = WSE_Array("C", shape=(8, 8, 6))
    with WSE_For_Loop("t", 1):
        T[1:-1, 0, 0] = T[1:-1, 0, 0] - 0.5 * C[1:-1, 0, 0] * T[2:, 0, 0]
    ops = list(wse.program.ops)
    wse.__exit__()
    group, name = lower_group(ops), "T"
    t = transpose_taps(group, name)
    assert transpose_taps(t, name) == group
    terms = [term for u in t.updates for term in u.terms if len(term[1]) == 2]
    (coeff, taps) = terms[0]
    by_field = {tap.field: tap for tap in taps}
    # the frontend's first index is the z-slice: T[2:, 0, 0] is a dz=+1 tap
    assert by_field["T"] == Tap("T", -1, 0, 0)
    assert by_field["C"] == Tap("C", -1, 0, 0)
    assert coeff == -0.5


def test_transpose_taps_rejects_nonlinear():
    wse = WSE_Interface()
    T = WSE_Array("T", shape=(8, 8, 6))
    with WSE_For_Loop("t", 1):
        T[1:-1, 0, 0] = T[1:-1, 0, 0] * T[2:, 0, 0]
    ops = list(wse.program.ops)
    wse.__exit__()
    group = lower_group(ops)
    with pytest.raises(LoweringError, match="not linear in the unknown"):
        transpose_taps(group, "T")


# -- differentiable-path validation errors ------------------------------------


def test_nonaffine_operator_raises_under_grad():
    """A body the lowering pass cannot canonicalize (degree three — would
    run on the interpreter fallback) must raise, not silently mis-gradient."""
    from repro.solver.frontend import Operator

    with scoped_program() as prog:
        T = Field("T", shape=(8, 8, 6), dtype=np.float32)
        with Operator():
            T[1:-1, 0, 0] = T[1:-1, 0, 0] * T[1:-1, 0, 0] * T[1:-1, 0, 0]
    with pytest.raises(ValueError, match="affine"):
        make_differentiable_solver(prog, "T")


def test_nonlinear_operator_raises_under_grad():
    from repro.solver.frontend import Operator

    with scoped_program() as prog:
        T = Field("T", shape=(8, 8, 6), dtype=np.float32)
        with Operator():
            T[1:-1, 0, 0] = T[1:-1, 0, 0] * T[2:, 0, 0]
    with pytest.raises(ValueError, match="nonlinear"):
        make_differentiable_solver(prog, "T")


def test_fixed_iteration_methods_rejected():
    prog = btcs_program((8, 8, 6), 0.2)
    with pytest.raises(ValueError, match="chebyshev"):
        make_differentiable_solver(prog, "T", method="chebyshev")
    assert "chebyshev" not in ADJOINT_METHODS


def test_make_solver_differentiable_rejects_batch():
    prog = btcs_program((8, 8, 6), 0.2)
    with pytest.raises(ValueError, match="batch=1"):
        make_solver(prog, "T", batch=2, differentiable=True)


def test_solve_differentiable_rejects_mesh():
    from repro.solver import solve

    prog = btcs_program((8, 8, 6), 0.2)
    with pytest.raises(ValueError, match="single-device"):
        solve(
            prog,
            "T",
            options=RunOptions(differentiable=True, mesh=object()),
        )


def test_solve_differentiable_route_matches_default():
    """options.differentiable=True must not change eager solve() numerics."""
    from repro.solver import record_btcs, solve

    T0 = heat_init((10, 10, 6))
    wse, T = record_btcs(T0, 0.2)
    x_ref = solve(wse.program, T, method="cg", tol=1e-6)
    wse2, T2 = record_btcs(T0, 0.2)
    x_diff = solve(
        wse2.program, T2, method="cg", tol=1e-6,
        options=RunOptions(differentiable=True),
    )
    assert (x_ref == x_diff).all()


# -- gradient checks (fp64 subprocesses) --------------------------------------

GRADCHECK_PREAMBLE = f"""
import sys
sys.path.insert(0, {os.path.join(ROOT, "tests")!r})
import jax
import jax.numpy as jnp
import numpy as np
from gradcheck import gradcheck
from repro.compiler import clear_cache, reset_stats, stats
from repro.core.field import Field
from repro.core.program import scoped_program
from repro.solver import make_differentiable_solver
from repro.solver.frontend import Operator, Rhs
from repro.solver.presets import _record_btcs_body, _record_poisson_body

rng = np.random.default_rng(0)
"""


def test_gradcheck_symmetric_methods_reuse_forward_kernel():
    """CG and PipeCG VJPs match FD at fp64; the backward solve builds ZERO
    new kernels (symmetric transpose == forward group) and hits the cache."""
    out = run_py(GRADCHECK_PREAMBLE + """
shape = (10, 12, 6)
w = jnp.asarray(rng.normal(size=shape))
x0 = jnp.asarray(rng.normal(size=shape))
for method in ("cg", "pipecg"):
    with scoped_program() as prog:
        T = Field("T", shape=shape, dtype=np.float64)
        _record_btcs_body(T, 0.3)
    clear_cache(); reset_stats()
    s = make_differentiable_solver(prog, "T", method=method, tol=1e-12, maxiter=400)
    assert s.symmetric_adjoint
    # ONE kernel serves forward and adjoint: the transposed group re-
    # canonicalized to the same cache key (the build's second compile hit)
    assert stats.kernels_built == 1, (method, stats.kernels_built)
    assert stats.cache_hits >= 1, method
    loss = jax.jit(lambda v, s=s: jnp.sum(w * s(v)))
    g = jax.grad(loss)(x0)
    jax.block_until_ready(g)
    assert stats.kernels_built == 1, (method, stats.kernels_built)
    assert stats.fallbacks == 0
    r = gradcheck(loss, x0, g, n_probes=8)
    assert r.ok, (method, str(r))
    print(method, "max scaled err", r.max_scaled_err)
print("PASS")
""", x64=True)
    assert "PASS" in out


def test_gradcheck_bicgstab_coefficient_and_state():
    """Non-symmetric variable-coefficient diffusion: the adjoint lowers the
    transposed tap set into ONE extra kernel, and both the coefficient-field
    and state gradients match FD at fp64."""
    out = run_py(GRADCHECK_PREAMBLE + """
shape = (10, 12, 6)
w = jnp.asarray(rng.normal(size=shape))
x0 = jnp.asarray(rng.normal(size=shape))
C0 = jnp.asarray(0.4 + 0.2 * rng.random(shape))
with scoped_program() as prog:
    T = Field("T", shape=shape, dtype=np.float64)
    C = Field("C", shape=shape, dtype=np.float64, init_data=np.asarray(C0))
    with Operator():
        T[1:-1, 0, 0] = T[1:-1, 0, 0] + 0.2 * C[1:-1, 0, 0] * (
            6.0 * T[1:-1, 0, 0]
            - (T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0]
               + T[1:-1, -1, 0] + T[1:-1, 0, 1] + T[1:-1, 0, -1]))
clear_cache(); reset_stats()
s = make_differentiable_solver(prog, "T", method="bicgstab", tol=1e-13, maxiter=600)
assert not s.symmetric_adjoint
assert stats.kernels_built == 2  # forward + transposed, both at build time
built = stats.kernels_built
loss_C = jax.jit(lambda c: jnp.sum(w * s(x0, {"C": c})))
g_C = jax.grad(loss_C)(C0)
assert stats.kernels_built == built  # grad reuses both cached kernels
r = gradcheck(loss_C, C0, g_C, n_probes=8)
assert r.ok, str(r)
loss_x = jax.jit(lambda v: jnp.sum(w * s(v, {"C": C0})))
g_x = jax.grad(loss_x)(x0)
r2 = gradcheck(loss_x, x0, g_x, n_probes=8)
assert r2.ok, str(r2)
assert stats.fallbacks == 0
print("PASS", r.max_scaled_err, r2.max_scaled_err)
""", x64=True)
    assert "PASS" in out


def test_gradcheck_multigrid_methods():
    """method='mg' and mg-preconditioned CG differentiate through the same
    cycle machinery (symmetric — reused verbatim in the backward solve)."""
    out = run_py(GRADCHECK_PREAMBLE + """
shape = (12, 12, 8)
F0 = rng.normal(size=shape)
w = jnp.asarray(rng.normal(size=shape))
x0 = jnp.asarray(rng.normal(size=shape))
for method, precond in (("mg", None), ("cg", "mg")):
    with scoped_program() as prog:
        T = Field("T", shape=shape, dtype=np.float64)
        Ff = Field("T_rhs", shape=shape, dtype=np.float64, init_data=F0)
        _record_poisson_body(T, Ff)
    clear_cache(); reset_stats()
    s = make_differentiable_solver(prog, "T", method=method,
                                   precondition=precond, tol=1e-13, maxiter=400)
    assert s.symmetric_adjoint
    built_after_build = stats.kernels_built
    loss = jax.jit(lambda f, s=s: jnp.sum(w * s(x0, {"T_rhs": f})))
    g = jax.grad(loss)(jnp.asarray(F0))
    jax.block_until_ready(g)
    assert stats.kernels_built == built_after_build, method
    r = gradcheck(loss, np.asarray(F0), g, n_probes=6)
    assert r.ok, (method, precond, str(r))
    assert stats.fallbacks == 0
    print(method, precond, "max scaled err", r.max_scaled_err)
print("PASS")
""", x64=True)
    assert "PASS" in out


# -- checkpointed reverse stepping --------------------------------------------


def _build_heat_program(T0, steps):
    with scoped_program() as prog:
        T = Field("T", init_data=T0, dtype=T0.dtype)
        with ForLoop("t", steps):
            T[1:-1, 0, 0] = 0.4 * T[1:-1, 0, 0] + 0.1 * (
                T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0]
                + T[1:-1, -1, 0] + T[1:-1, 0, 1] + T[1:-1, 0, -1]
            )
    return prog


def _runner_grad(T0, w, steps, time_tile, checkpoint, chunk_steps=None):
    p = plan(
        _build_heat_program(T0, steps),
        options=RunOptions(
            backend="pallas", differentiable=True, time_tile=time_tile
        ),
    )
    run = differentiable_runner(p, checkpoint=checkpoint, chunk_steps=chunk_steps)
    loss = lambda env: jnp.sum(jnp.asarray(w) * run(env)["T"])
    return np.asarray(jax.grad(loss)({"T": jnp.asarray(T0)})["T"])


def _assert_ulp_close(a, b, ulps=4.0):
    scale = max(np.abs(a).max(), np.abs(b).max())
    tol = ulps * scale * np.finfo(a.dtype).eps
    assert np.abs(a - b).max() <= tol, np.abs(a - b).max() / (scale * np.finfo(a.dtype).eps)


@pytest.mark.parametrize("time_tile,steps", [(1, 9), (2, 13), (4, 13), (4, 16)])
def test_checkpointed_runner_grad_matches_reference(rng, time_tile, steps):
    """Checkpointed reverse stepping == all-residuals reference to ~ulp,
    across time-tile factors (13 = remainder steps for k∈{2,4}).  fp32
    in-process; the fp64 variant runs in the sharded subprocess test."""
    T0 = rng.normal(size=(10, 8, 6)).astype(np.float32)
    w = rng.normal(size=(10, 8, 6)).astype(np.float32)
    ref = _runner_grad(T0, w, steps, 1, checkpoint=False)
    got = _runner_grad(T0, w, steps, time_tile, checkpoint=True)
    _assert_ulp_close(got, ref, ulps=8.0)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional test extra
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @given(
        steps=st.integers(1, 18),
        time_tile=st.sampled_from([1, 2, 4]),
        chunk_steps=st.sampled_from([None, 2, 5]),
        seed=st.integers(0, 10**6),
    )
    @settings(deadline=None, max_examples=15)
    def test_checkpointed_runner_grad_property(steps, time_tile, chunk_steps, seed):
        r = np.random.default_rng(seed)
        T0 = r.normal(size=(8, 8, 5)).astype(np.float32)
        w = r.normal(size=(8, 8, 5)).astype(np.float32)
        ref = _runner_grad(T0, w, steps, 1, checkpoint=False)
        got = _runner_grad(T0, w, steps, time_tile, True, chunk_steps)
        _assert_ulp_close(got, ref, ulps=8.0)


def test_ftcs_checkpointed_matches_plain(rng):
    T0 = jnp.asarray(rng.normal(size=(10, 10, 6)))
    w = jnp.asarray(rng.normal(size=(10, 10, 6)))
    for steps in (1, 5, 12, 16):
        a = np.asarray(ftcs_solve(T0, 0.1, steps))
        b = np.asarray(ftcs_solve_checkpointed(T0, 0.1, steps))
        _assert_ulp_close(a, b, ulps=2.0)
    g_ck = jax.grad(lambda t: jnp.sum(w * ftcs_solve_checkpointed(t, 0.1, 13)))(T0)
    g_nc = jax.grad(lambda t: jnp.sum(w * ftcs_solve(t, 0.1, 13)))(T0)
    _assert_ulp_close(np.asarray(g_ck), np.asarray(g_nc))


def test_gradcheck_harness_on_explicit_stepper(rng):
    """The FD harness itself, exercised end-to-end on the explicit path."""
    T0 = rng.normal(size=(8, 8, 5))
    w = jnp.asarray(rng.normal(size=(8, 8, 5)))
    loss = lambda t: float(jnp.sum(w * ftcs_solve_checkpointed(jnp.asarray(t), 0.1, 7)))
    g = jax.grad(lambda t: jnp.sum(w * ftcs_solve_checkpointed(t, 0.1, 7)))(
        jnp.asarray(T0)
    )
    # fp32 in-process: loosen to the fp32 FD noise floor (the tight fp64
    # tolerances are exercised by the subprocess gradchecks above)
    assert_gradcheck(loss, T0, np.asarray(g), eps=1e-2, atol=1e-2, rtol=5e-2)


def test_probe_points_mix_boundary_and_interior():
    pts = probe_points((6, 7, 5), 10, seed=1)
    assert len(pts) == 10
    assert any(0 in p or p[0] == 5 or p[1] == 6 or p[2] == 4 for p in pts)
    assert any(all(0 < c for c in p) for p in pts[5:])


# -- donation under AD --------------------------------------------------------


def test_donation_suppressed_under_differentiable_plan():
    """differentiable=True plans must not donate: no donation markers in the
    compiled HLO and the caller's entry buffers stay alive."""
    T0 = heat_init()
    wse = WSE_Interface()
    T = WSE_Array("T_n", init_data=T0)
    with WSE_For_Loop("t", 4):
        T[1:-1, 0, 0] = 0.4 * T[1:-1, 0, 0] + 0.1 * (
            T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0]
            + T[1:-1, 0, -1] + T[1:-1, -1, 0] + T[1:-1, 0, 1]
        )
    try:
        p = plan(wse.program, options=RunOptions(backend="pallas", differentiable=True))
        p_ref = plan(wse.program, options=RunOptions(backend="pallas"))
    finally:
        wse.__exit__()
    assert p.differentiable and not p_ref.differentiable
    runner = single_runner(p)
    env = {"T_n": jnp.asarray(T0)}
    lowered = runner.lower(env).as_text()
    assert "jax.buffer_donor" not in lowered
    assert "tf.aliasing_output" not in lowered
    out = runner(env)
    jax.block_until_ready(out["T_n"])
    assert not env["T_n"].is_deleted()
    # and the same program WITHOUT differentiable still donates
    ref_lowered = single_runner(p_ref).lower({"T_n": jnp.asarray(T0)}).as_text()
    assert "jax.buffer_donor" in ref_lowered or "tf.aliasing_output" in ref_lowered


def test_differentiable_runner_requires_flag():
    T0 = heat_init((8, 8, 6))
    p = plan(
        _build_heat_program(T0, 4),
        options=RunOptions(backend="pallas"),
    )
    with pytest.raises(ValueError, match="differentiable"):
        differentiable_runner(p)


# -- sharded gradient parity (fp64 subprocess) --------------------------------


def test_sharded_gradient_matches_single_device_fp64():
    """2×2-mesh gradient of the differentiable runner vs single device:
    forward bitwise, gradient within a few ulps (sharded VJP reduction
    order), donation nowhere in sight."""
    out = run_py("""
import jax
import jax.numpy as jnp
import numpy as np
import repro as wfa
from repro.core.field import Field
from repro.core.program import ForLoop, scoped_program
from repro.engine import differentiable_runner, plan

rng = np.random.default_rng(0)
T0 = rng.normal(size=(12, 8, 6))
w = jnp.asarray(rng.normal(size=(12, 8, 6)))

def build():
    with scoped_program() as prog:
        T = Field("T", init_data=T0, dtype=np.float64)
        with ForLoop("t", 9):
            T[1:-1, 0, 0] = 0.4 * T[1:-1, 0, 0] + 0.1 * (
                T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0]
                + T[1:-1, -1, 0] + T[1:-1, 0, 1] + T[1:-1, 0, -1])
    return prog

from repro.core.jaxcompat import make_mesh
mesh = make_mesh((2, 2), ("x", "y"))
opts = wfa.RunOptions(backend="pallas", differentiable=True)
r1 = differentiable_runner(plan(build(), options=opts))
r2 = differentiable_runner(plan(build(), options=opts.replace(mesh=mesh)))
env0 = {"T": jnp.asarray(T0)}
o1, o2 = r1(env0)["T"], r2(env0)["T"]
assert (np.asarray(o1) == np.asarray(o2)).all()
g1 = jax.grad(lambda e: jnp.sum(w * r1(e)["T"]))(env0)["T"]
g2 = jax.grad(lambda e: jnp.sum(w * r2(e)["T"]))(env0)["T"]
scale = float(jnp.abs(g1).max())
assert float(jnp.abs(g1 - g2).max()) <= 4 * scale * np.finfo(np.float64).eps
assert not env0["T"].is_deleted()
print("PASS")
""", devices=4, x64=True)
    assert "PASS" in out


def test_checkpointed_vjp_spill_matches_in_memory_fp64(tmp_path):
    """Out-of-core reverse sweep: disk-spilled chunk snapshots give the
    same gradient as host-memory snapshots and as plain jax.vjp."""
    out = run_py(f"""
import jax
import jax.numpy as jnp
import numpy as np
from repro.core.explicit import ftcs_step
from repro.engine import checkpointed_vjp

rng = np.random.default_rng(0)
env0 = {{"T": jnp.asarray(rng.normal(size=(10, 10, 5)))}}
w = jnp.asarray(rng.normal(size=(10, 10, 5)))
chunk = lambda env: {{"T": ftcs_step(ftcs_step(env["T"], 0.1), 0.1)}}
final, vjp = checkpointed_vjp(chunk, env0, 6)
ct = jax.tree.map(jnp.zeros_like, final); ct["T"] = w
g_mem = vjp(ct)
final2, vjp2 = checkpointed_vjp(chunk, env0, 6, spill_dir={str(tmp_path)!r})
g_disk = vjp2(ct)

def f(env):
    for _ in range(6):
        env = chunk(env)
    return env

ref, pb = jax.vjp(f, env0)
(g_ref,) = pb(ct)
assert (np.asarray(final["T"]) == np.asarray(ref["T"])).all()
assert (np.asarray(g_mem["T"]) == np.asarray(g_ref["T"])).all()
assert (np.asarray(g_disk["T"]) == np.asarray(g_ref["T"])).all()
print("PASS")
""", x64=True)
    assert "PASS" in out
