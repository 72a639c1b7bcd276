"""Implicit BTCS heat solver (paper Eq. 3) — legacy drivers over the solver
subsystem.

``A = I − ωψ·S`` with ``S`` the 6-neighbour sum and ``ψ = 1/(1+6ω)``; identity
rows on boundary cells.  CG runs on the interior subspace: search vectors are
zero on the Moat, so the masked operator is SPD there.

Since the solver subsystem landed (:mod:`repro.solver`) there is ONE
operator-compilation path: the BTCS operator is *recorded* through the WFA
frontend (:func:`repro.solver.presets.btcs_program`) and applied via the
shared program step — the same body ``wfa.solve`` lowers to a fused Pallas
kernel — and every iteration lives in :mod:`repro.solver.krylov`.  This
module keeps the historical driver surface:

* :func:`btcs_solve` — single-device time stepping (CG, pipelined CG,
  BiCGSTAB, Chebyshev, Jacobi);
* :func:`make_sharded_implicit` — brick-sharded drivers over a device mesh
  (kernel or interpreter operator application, fused ``psum`` reductions);
* :func:`make_operator` / :func:`make_brick_operator` — raw operator
  builders (the brick variant backs the roofline iteration harness).
"""
from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.explicit import interior_mask3d, neighbor_sum_padded
from repro.core.halo import halo_pad, local_moat_mask
from repro.core.jaxcompat import shard_map
from repro.solver import krylov
from repro.solver.api import make_sharded_solver, operator_fns
from repro.solver.presets import btcs_program, psi

__all__ = [
    "bicgstab_solve", "btcs_solve", "cg_solve", "chebyshev_bounds",
    "chebyshev_solve", "jacobi_solve", "make_brick_operator",
    "make_operator", "make_sharded_implicit", "make_sharded_iteration",
    "pipecg_solve", "psi",
]

# the Krylov/relaxation iterations, re-exported under their legacy names
# (one shared implementation — see repro.solver.krylov)
cg_solve = krylov.cg
pipecg_solve = krylov.pipecg
bicgstab_solve = krylov.bicgstab
chebyshev_solve = krylov.chebyshev
jacobi_solve = krylov.jacobi

#: legacy entry points that already warned this process (warn once each)
_DEPRECATION_WARNED = set()


def _warn_legacy(fn: str) -> None:
    if fn in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(fn)
    warnings.warn(
        f"repro.core.implicit.{fn} is deprecated; record the system through "
        "the WFA frontend (repro.solver presets) and call wfa.solve — "
        "repro.solver.solve / WFAInterface.solve — instead",
        DeprecationWarning,
        stacklevel=3,
    )


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def _make_operator(w: float, shape):
    A, rhs = operator_fns(btcs_program(shape, w), "T", backend="jit")
    mask = interior_mask3d(shape)

    def dot(a, b):
        return jnp.sum(a * b, dtype=jnp.float32)

    return A, rhs, dot, mask


def make_operator(w: float, shape):
    """Single-device masked BTCS operator and rhs builder.

    .. deprecated:: use ``wfa.solve`` (or :func:`repro.solver.operator_fns`
       for raw applications) — this shim warns once and forwards.

    The operator body is recorded through the WFA frontend and applied with
    the shared program step (``repro.solver.api.operator_fns``), so this
    hand-callable path and the compiled ``wfa.solve`` path execute the same
    recorded stencil.
    """
    _warn_legacy("make_operator")
    return _make_operator(w, shape)


def make_brick_operator(w: float, brick_shape, ax_x, ax_y, mx, my,
                        use_kernel: bool = False):
    """Brick-local operator for use inside ``shard_map``.

    SpMV = halo exchange + padded stencil; dot = local dot + ``psum`` over
    both mesh axes (the reduction-to-center analogue, Fig. 2c).  Kept as the
    raw building block for the roofline iteration harness
    (:func:`make_sharded_iteration`); the time-stepping drivers go through
    ``repro.solver`` instead.
    """
    bx, by, nz = brick_shape
    wpsi = w * psi(w)
    if use_kernel:
        from repro.kernels import ops as kops

    def mask():
        m2 = local_moat_mask(bx, by, ax_x, ax_y, mx, my)
        zi = jax.lax.broadcasted_iota(jnp.int32, (1, 1, nz), 2)
        return m2 & (zi > 0) & (zi < nz - 1)

    def A(v):
        P = halo_pad(v, 1, ax_x, ax_y, mx, my)
        if use_kernel:
            Av = kops.spmv_hex(P, 1.0, -wpsi)
        else:
            Av = v - wpsi * neighbor_sum_padded(P)
        return jnp.where(mask(), Av, v)

    def rhs(T):
        return jnp.where(mask(), psi(w) * T, T)

    def dot(a, b):
        d = jnp.sum(a * b, dtype=jnp.float32)
        # joint-axis psum: ONE all-reduce over the whole mesh instead of two
        # chained single-axis reductions — halves the diameter-latency term
        # (§Perf heat-implicit iteration 1)
        return jax.lax.psum(d, (ax_x, ax_y))

    return A, rhs, dot, mask


def chebyshev_bounds(w: float):
    """Analytic eigenvalue bounds of A = I − ωψS on the interior subspace.

    The neighbour-sum S on a Dirichlet grid has spectrum in (−6, 6), so
    λ(A) ⊂ [1−6ωψ, 1+6ωψ].  With the paper's ω = 0.1: [0.625, 1.375].
    (``repro.solver`` derives the same bracket mechanically from the lowered
    tap form — Gershgorin circles; see ``gershgorin_bounds``.)
    """
    wp = w * psi(w)
    return 1.0 - 6.0 * wp, 1.0 + 6.0 * wp


# ---------------------------------------------------------------------------
# time-stepping drivers
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("w", "steps", "method", "tol", "maxiter"))
def _btcs_solve_impl(T0, w: float, steps: int, method: str = "cg",
                     tol: float = 1e-6, maxiter: int = 500):
    A, rhs, dot, mask = _make_operator(w, T0.shape)

    def dot2(a, b, c, d):
        return dot(a, b), dot(c, d)

    def one(T, _):
        b = rhs(T)
        # the legacy aux contract stays (i, res); the outcome word is the
        # wfa.solve path's surface (SolveInfo.outcomes)
        if method == "cg":
            x, i, res, _ = krylov.cg(A, dot, b, T, tol=tol, maxiter=maxiter)
        elif method == "pipecg":
            x, i, res, _ = krylov.pipecg(A, dot2, b, T, tol=tol,
                                         maxiter=maxiter)
        elif method == "bicgstab":
            x, i, res, _ = krylov.bicgstab(A, dot, b, T, tol=tol,
                                           maxiter=maxiter)
        elif method == "chebyshev":
            lmin, lmax = chebyshev_bounds(w)
            x, i, res, _ = krylov.chebyshev(A, b, T, lmin, lmax,
                                            iters=maxiter)
        elif method == "jacobi":
            # unit diagonal + identity Moat rows: x + b − A(x) IS the Jacobi
            # sweep (b + ωψ·Sx interior, b on the Moat) — no mask needed
            x, i, res, _ = krylov.jacobi(lambda x: x + b - A(x), T,
                                         iters=maxiter)
        else:
            raise ValueError(method)
        return x, (i, res)

    T, aux = jax.lax.scan(one, T0, None, length=steps)
    return T, aux


def btcs_solve(T0, w: float, steps: int, method: str = "cg",
               tol: float = 1e-6, maxiter: int = 500):
    """Advance `steps` BTCS time steps on a single device.

    .. deprecated:: record the system (``repro.solver.record_btcs``) and
       call ``wfa.solve`` — same kernels, full method/preconditioner
       surface, ensemble batching.  This shim warns once and forwards.
    """
    _warn_legacy("btcs_solve")
    return _btcs_solve_impl(T0, w, steps, method=method, tol=tol,
                            maxiter=maxiter)


def make_sharded_implicit(mesh, shape, w: float, *, method: str = "cg",
                          tol: float = 1e-6, maxiter: int = 500,
                          use_kernel: bool = False, steps: int = 1):
    """Brick-sharded BTCS solver over ``mesh``; returns (step_fn, sharding).

    .. deprecated:: use ``wfa.solve(..., mesh=...)`` /
       :func:`repro.solver.make_sharded_solver` — this shim warns once and
       forwards.

    Routed through ``repro.solver.make_sharded_solver``: the recorded BTCS
    body compiles to one fused Pallas kernel per operator application when
    ``use_kernel`` (the PR-1 compiler path, inside shard_map) or runs on the
    shared roll interpreter otherwise; reductions are one fused ``psum``.
    """
    _warn_legacy("make_sharded_implicit")
    backend = "pallas" if use_kernel else "jit"
    step, sharding = make_sharded_solver(
        btcs_program(shape, w), "T", mesh, method=method, backend=backend,
        tol=tol, maxiter=maxiter, steps=steps)

    def step_fn(T):
        return step(T)[0]

    return step_fn, sharding


# ---------------------------------------------------------------------------
# roofline iteration harness (exact per-iteration accounting)
# ---------------------------------------------------------------------------

def make_sharded_iteration(mesh, shape, w: float, *, method: str = "cg",
                           use_kernel: bool = False):
    """One inner iteration as a standalone jitted step (for exact roofline
    accounting: no solver setup, no replacement branch).  State pytrees:

        cg:        (x, r, p, rr)
        pipecg:    (x, r, w, z, p, s, gamma, alpha)
        chebyshev: (x, r, d, rho)
    """
    ax_x, ax_y = mesh.axis_names[-2], mesh.axis_names[-1]
    mx, my = mesh.shape[ax_x], mesh.shape[ax_y]
    nx, ny, nz = shape
    bx, by = nx // mx, ny // my
    spec = jax.sharding.PartitionSpec(ax_x, ax_y, None)
    sharding = jax.sharding.NamedSharding(mesh, spec)
    vec = lambda: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    scal = lambda: jax.ShapeDtypeStruct((), jnp.float32)

    def local(state):
        A, rhs, dot, _ = make_brick_operator(
            w, (bx, by, nz), ax_x, ax_y, mx, my, use_kernel=use_kernel)

        def dot2(a, b, c, d):
            from repro.kernels import ops as kops
            part = jax.lax.psum(kops.dual_dot(a, b, c, d), (ax_x, ax_y))
            return part[0], part[1]

        if method == "cg":
            x, r, p, rr = state
            if use_kernel:
                from repro.kernels import ops as kops
                P = halo_pad(p, 1, ax_x, ax_y, mx, my)
                Ap, pAp_l = kops.spmv_hex_dot(P, 1.0, -w * psi(w))
                Ap = jnp.where(_mask(bx, by, nz, ax_x, ax_y, mx, my), Ap, p)
                pAp = jax.lax.psum(pAp_l, (ax_x, ax_y))
            else:
                Ap = A(p)
                pAp = dot(p, Ap)
            alpha = rr / pAp
            x = x + alpha * p
            r = r - alpha * Ap
            rr_new = dot(r, r)
            beta = rr_new / rr
            p = r + beta * p
            return (x, r, p, rr_new)
        if method == "pipecg":
            x, r, w_, z, p, sv, gamma_prev, alpha_prev = state
            gamma, delta = dot2(r, r, w_, r)
            n = A(w_)
            beta = gamma / gamma_prev
            alpha = gamma / (delta - beta * gamma / alpha_prev)
            z = n + beta * z
            p = r + beta * p
            sv = w_ + beta * sv
            x = x + alpha * p
            r = r - alpha * sv
            w_ = w_ - alpha * z
            return (x, r, w_, z, p, sv, gamma, alpha)
        if method == "chebyshev":
            x, r, d, rho = state
            lmin, lmax = chebyshev_bounds(w)
            theta = 0.5 * (lmax + lmin)
            delta = 0.5 * (lmax - lmin)
            sigma1 = theta / delta
            r = r - A(d)
            rho_new = 1.0 / (2.0 * sigma1 - rho)
            d = rho_new * rho * d + (2.0 * rho_new / delta) * r
            x = x + d
            return (x, r, d, rho_new)
        raise ValueError(method)

    n_vec = {"cg": 3, "pipecg": 6, "chebyshev": 3}[method]
    n_scal = {"cg": 1, "pipecg": 2, "chebyshev": 1}[method]
    state_sds = tuple([vec() for _ in range(n_vec)]
                      + [scal() for _ in range(n_scal)])
    vspec = spec
    sspec = jax.sharding.PartitionSpec()
    state_spec = tuple([vspec] * n_vec + [sspec] * n_scal)
    step = jax.jit(shard_map(local, mesh=mesh, in_specs=(state_spec,),
                                 out_specs=state_spec, check=False))
    return step, state_sds


def _mask(bx, by, nz, ax_x, ax_y, mx, my):
    m2 = local_moat_mask(bx, by, ax_x, ax_y, mx, my)
    zi = jax.lax.broadcasted_iota(jnp.int32, (1, 1, nz), 2)
    return m2 & (zi > 0) & (zi < nz - 1)
