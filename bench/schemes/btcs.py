"""The paper's Eq. 3 implicit heat system (BTCS) on one chip.

``A = I − ωψ·S`` on the interior with identity rows on the Moat,
``b = ψ·Tⁿ`` on the interior (``ψ = 1/(1 + 6ω)``), solved from ``x₀ = Tⁿ``.
Configuration keys: ``grid``, ``dtype``, ``omega``, ``plate`` and
``solver`` (``method``, ``tol``, ``maxiter``).

* :func:`btcs_residual` is the plain reference (slicing; it imports
  nothing of the program) and :func:`compare_solve` the comparison of one
  answer with it; :func:`btcs_cg` is textbook CG, the control;
* :func:`inputs` makes the seeded hot plates ``Tⁿ``;
* :func:`solver` is what the ``solving`` loop drives: ``make_solver`` on
  the recorded system, or, for the control, :func:`btcs_cg` one precision
  down.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import fields
from bench.harness.generator import LOWER, Solver

# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _apply(x, w: float):
    """``A·x`` with plain slicing."""
    psi = 1.0 / (1.0 + 6.0 * w)
    c = (slice(1, -1),) * 3
    S = (x[2:, 1:-1, 1:-1] + x[:-2, 1:-1, 1:-1] + x[1:-1, 2:, 1:-1]
         + x[1:-1, :-2, 1:-1] + x[1:-1, 1:-1, 2:] + x[1:-1, 1:-1, :-2])
    return x.at[c].add(jnp.asarray(-w * psi, x.dtype) * S)


def _rhs(T0, w: float):
    psi = 1.0 / (1.0 + 6.0 * w)
    return T0.at[(slice(1, -1),) * 3].multiply(jnp.asarray(psi, T0.dtype))


@partial(jax.jit, static_argnames=("w",))
def btcs_residual(x, T0, w: float):
    """Relative true residual, computed in float32: ``(‖b − A·x‖₂ / ‖b‖₂,
    max|b − A·x| / max|b|)``.  The second sees an error in a single cell,
    which the first dilutes over the grid."""
    x = x.astype(jnp.float32)
    b = _rhs(T0.astype(jnp.float32), w)
    r = b - _apply(x, w)
    return (jnp.sqrt(jnp.sum(r * r) / jnp.sum(b * b)),
            jnp.max(jnp.abs(r)) / jnp.max(jnp.abs(b)))


def compare_solve(config: dict, T0, x) -> dict:
    two, inf = jax.device_get(btcs_residual(x, T0, float(config["omega"])))
    return {"true_residual": float(two), "true_residual_max": float(inf)}


@partial(jax.jit, static_argnames=("w", "tol", "maxiter", "dtype"))
def btcs_cg(T0, w: float, tol: float, maxiter: int, dtype="float32"):
    """Textbook CG in ``dtype`` from ``x₀ = Tⁿ``, stopping at ``‖r‖₂ ≤ tol``
    or ``maxiter``; returns ``(x, iterations, converged, ‖r‖₂)``."""
    dt = jnp.dtype(dtype)
    x = T0.astype(dt)
    b = _rhs(x, w)
    r = b - _apply(x, w)
    rr = jnp.sum(r * r)

    def cond(s):
        _, _, _, rr, i = s
        return (rr > tol * tol) & (i < maxiter)

    def body(s):
        x, r, p, rr, i = s
        Ap = _apply(p, w)
        alpha = rr / jnp.sum(p * Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        rr_new = jnp.sum(r * r)
        return x, r, r + (rr_new / rr) * p, rr_new, i + 1

    x, _, _, rr, i = jax.lax.while_loop(cond, body, (x, r, r, rr, 0))
    rr = rr.astype(jnp.float32)
    return x.astype(jnp.float32), i, rr <= tol * tol, jnp.sqrt(rr)


def inputs(config: dict, seed: int, count: int):
    return fields.plates(seed, config["grid"], count, config["plate"], config["dtype"])


# ---------------------------------------------------------------------------
# the system under test and its control
# ---------------------------------------------------------------------------


def solver(config: dict, traffic: dict, devices, control: bool) -> Solver:
    s = config["solver"]
    w, tol, maxiter = float(config["omega"]), float(s["tol"]), int(s["maxiter"])
    if control:
        low = LOWER[config["dtype"]]

        def solve(T0):
            x, iters, conv, res = btcs_cg(T0, w, tol, maxiter, low)
            return x, (iters, conv, res)

        def read(raw):
            iters, conv, res = raw
            return int(iters), bool(conv), float(res)

        return Solver(solve=solve, read=read, info={"control": low})
    from repro.solver.api import make_solver
    from repro.solver.health import CONVERGED
    from repro.solver.presets import btcs_program

    fn = make_solver(btcs_program(tuple(config["grid"]), w), "T", method=s["method"],
                     tol=tol, maxiter=maxiter)

    def read(raw):
        iters, res, outcomes = (np.ravel(a)[0] for a in raw)
        return int(iters), int(outcomes) == CONVERGED, float(res)

    return Solver(solve=fn, read=read, info={"method": s["method"]})
