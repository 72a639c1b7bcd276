"""Matrix-free Krylov and relaxation iterations, generic over ``(A, dot)``.

One implementation serves every operator-compilation path: the legacy BTCS
drivers in :mod:`repro.core.implicit` and the ``wfa.solve`` frontend both
dispatch here, on one chip or inside ``shard_map`` (the ``dot`` callable owns
the ``psum``), with the operator ``A`` supplied as a plain function — a
compiled fused Pallas kernel, the roll interpreter, or anything else.

Methods and their per-iteration reduction count (the paper's Eq. 16/17
latency term):

* :func:`cg`        — classic CG, 2 reductions (SPD operators);
* :func:`pipecg`    — Ghysels–Vanroose pipelined CG, 1 fused reduction
  overlapped with the next SpMV;
* :func:`bicgstab`  — van der Vorst BiCGSTAB, 4 reductions, 2 operator
  applications (the workhorse for non-symmetric systems, e.g.
  variable-coefficient implicit diffusion);
* :func:`chebyshev` — reduction-free Chebyshev iteration (needs eigenvalue
  bounds of ``A``);
* :func:`jacobi`    — reduction-free Jacobi relaxation (needs the diagonal);
* :func:`stationary` — generic fixed-point iteration with a residual-norm
  stop — the driver behind ``method="mg"`` (one step = one V/W-cycle).

:func:`cg` and :func:`bicgstab` accept a preconditioner ``M`` (a linear
callable approximating ``A⁻¹`` — ``wfa.solve(precondition="mg")`` passes a
multigrid cycle from a zero guess); CG needs ``M`` symmetric positive
definite, BiCGSTAB is preconditioned from the right so any fixed linear
``M`` works.

Every method returns ``(x, iterations, ‖r‖, outcome)`` — the outcome is an
int32 word from the :mod:`repro.solver.health` taxonomy (``CONVERGED`` /
``MAXITER`` / ``NAN_RESIDUAL`` / ``BREAKDOWN`` / ``STAGNATED`` /
``DIVERGED``), per member (shape ``(B,)``) for the batched variants.  The
guard lives *inside* the ``while_loop`` carry at zero extra reductions: a
NaN residual used to make ``rr > tol*tol`` False, silently exiting the
loop and reporting the poisoned iterate as converged — now every exit is
classified, and hopeless iterations (divergence, stagnation, BiCGSTAB
breakdown) stop early instead of burning the ``maxiter`` budget.  Pass
``guard=GuardConfig(...)`` to tune the windows.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.solver import health

_TINY = 1e-30

#: device scope of CG's vector updates (the caller's ``dot`` names its
#: reductions ``wfa.krylov.dot``)
UPDATE = "wfa.krylov.update"


def _nonzero(d):
    """Clamp a denominator away from zero, keeping its sign (fp32 guard)."""
    return jnp.where(jnp.abs(d) < _TINY, jnp.where(d < 0, -_TINY, _TINY), d)


def cg(
    A: Callable,
    dot: Callable,
    b,
    x0,
    *,
    tol: float = 1e-6,
    maxiter: int = 500,
    M: Callable = None,
    dot2: Callable = None,
    guard: health.GuardConfig = None,
):
    """Classic CG.  Two reductions per iteration: (p, Ap) and (r, r) — the
    paper's benchmarked bottleneck.

    With a preconditioner ``M`` (symmetric positive definite, e.g. one
    multigrid cycle from a zero guess) this is standard PCG, stopping still
    on the *true* residual norm so iteration counts stay comparable to the
    plain method.  The two M-side reductions (r, z) and (r, r) are fused
    through ``dot2(a, b, c, d) -> (a·b, c·d)`` when the caller provides it
    (sharded backends: ONE ``psum`` instead of two — the Eq. 16 latency
    term), falling back to two ``dot`` calls otherwise.  All loop state
    lives in the ``while_loop`` carry, which XLA buffer-aliases in place —
    callers donate their entry buffers (``jax.jit(...,
    donate_argnums=...)``) so the whole iteration is allocation-free.
    """
    guard = guard or health.DEFAULT_GUARD
    Ax0 = A(x0)
    with jax.named_scope(UPDATE):
        r = b - Ax0
    if M is None:
        p = r
        rr = dot(r, r)
        g0 = health.guard_init(rr)

        def cond(s):
            x, r, p, rr, i, g = s
            return health.running(g) & (rr > tol * tol) & (i < maxiter)

        def body(s):
            x, r, p, rr, i, g = s
            Ap = A(p)
            pAp = dot(p, Ap)  # reduction 1
            with jax.named_scope(UPDATE):
                alpha = rr / pAp
                x = x + alpha * p
                r = r - alpha * Ap
            rr_new = dot(r, r)  # reduction 2 (overlaps x-update)
            with jax.named_scope(UPDATE):
                beta = rr_new / rr
                p = r + beta * p
            g = health.guard_update(g, rr_new, config=guard)
            return (x, r, p, rr_new, i + 1, g)

        x, r, p, rr, i, g = jax.lax.while_loop(cond, body, (x0, r, p, rr, 0, g0))
        return x, i, jnp.sqrt(rr), health.classify(g, rr, tol * tol)

    if dot2 is None:
        dot2 = lambda a, b_, c, d: (dot(a, b_), dot(c, d))  # noqa: E731
    z = M(r)
    p = z
    rz, rr = dot2(r, z, r, r)
    g0 = health.guard_init(rr)

    def pcond(s):
        x, r, p, rz, rr, i, g = s
        return health.running(g) & (rr > tol * tol) & (i < maxiter)

    def pbody(s):
        x, r, p, rz, rr, i, g = s
        Ap = A(p)
        pAp = dot(p, Ap)
        with jax.named_scope(UPDATE):
            alpha = rz / _nonzero(pAp)
            x = x + alpha * p
            r = r - alpha * Ap
        z = M(r)
        rz_new, rr_new = dot2(r, z, r, r)  # ONE fused reduction
        with jax.named_scope(UPDATE):
            beta = rz_new / _nonzero(rz)
            p = z + beta * p
        g = health.guard_update(g, rr_new, config=guard)
        return (x, r, p, rz_new, rr_new, i + 1, g)

    x, r, p, rz, rr, i, g = jax.lax.while_loop(
        pcond, pbody, (x0, r, p, rz, rr, 0, g0)
    )
    return x, i, jnp.sqrt(rr), health.classify(g, rr, tol * tol)


def pipecg(
    A: Callable,
    dot2: Callable,
    b,
    x0,
    *,
    tol: float = 1e-6,
    maxiter: int = 500,
    guard: health.GuardConfig = None,
):
    """Ghysels–Vanroose pipelined CG: ONE fused reduction per iteration,
    overlapped with the next SpMV.

    ``dot2(a, b, c, d)`` returns (a·b, c·d) in a single reduction — sharded
    backends implement it as one ``psum`` of a length-2 vector, halving the
    Eq. 16 latency term; XLA then schedules ``n = A w`` while it completes.
    """
    guard = guard or health.DEFAULT_GUARD
    r = b - A(x0)
    w_ = A(r)
    zero = jnp.zeros_like(b)
    rr0 = dot2(r, r, r, r)[0]  # true entry residual (warm-start guard)
    replace_every = 25  # periodic residual replacement (fp32 drift)

    def body2(s):
        x, r, w_, z, p, sv, gamma_prev, alpha_prev, i, fresh, g = s
        gamma, delta = dot2(r, r, w_, r)  # fused reduction
        n = A(w_)  # overlapped SpMV
        beta = jnp.where(fresh, 0.0, gamma / gamma_prev)
        denom = delta - beta * gamma / jnp.where(fresh, 1.0, alpha_prev)
        # fp32 pipelined recurrences can hit a vanishing denominator near
        # convergence; clamp to keep the iterate finite (cond exits next).
        denom = _nonzero(denom)
        alpha = gamma / denom
        z = n + beta * z
        p = r + beta * p
        sv = w_ + beta * sv
        x = x + alpha * p
        r = r - alpha * sv
        w_ = w_ - alpha * z
        # residual replacement: resync the recurred r/w with the true
        # residual every k iterations (Cools & Vanroose) — two extra SpMVs,
        # amortised 2/k, restores attainable accuracy at warm starts.
        do = (i + 1) % replace_every == 0
        r, w_ = jax.lax.cond(
            do,
            lambda x, r, w_: (b - A(x), A(b - A(x))),
            lambda x, r, w_: (r, w_),
            x,
            r,
            w_,
        )
        g = health.guard_update(g, gamma, config=guard)
        return (x, r, w_, z, p, sv, gamma, alpha, i + 1, do, g)

    def cond2(s):
        gamma_prev, i, g = s[6], s[8], s[10]
        # gamma_prev is ‖r‖² of the previous iterate (true rr0 at entry)
        return health.running(g) & (gamma_prev > tol * tol) & (i < maxiter)

    s0 = (
        x0,
        r,
        w_,
        zero,
        zero,
        zero,
        rr0,
        jnp.asarray(1.0, rr0.dtype),  # alpha carries the dot's dtype
        jnp.asarray(0, jnp.int32),
        jnp.asarray(True),
        health.guard_init(rr0),
    )
    out = jax.lax.while_loop(cond2, body2, s0)
    x, i, g = out[0], out[8], out[10]
    # one extra reduction per *solve* (not per iteration): the recurred
    # residual drifts, so classify on the recomputed true norm
    rr = dot2(out[1], out[1], out[1], out[1])[0]
    return x, i, jnp.sqrt(rr), health.classify(g, rr, tol * tol)


def bicgstab(
    A: Callable,
    dot: Callable,
    b,
    x0,
    *,
    tol: float = 1e-6,
    maxiter: int = 500,
    M: Callable = None,
    guard: health.GuardConfig = None,
):
    """van der Vorst BiCGSTAB — matrix-free, no transpose applications.

    The paper's workhorse for non-symmetric systems (upwind advection,
    variable-coefficient implicit diffusion).  Two operator applications and
    four reductions per iteration; the ``dot`` callable owns the all-reduce,
    so the same code runs on 1 chip or a full mesh.  An optional ``M``
    preconditions from the *right* (``A M y = b``, ``x = M y``), so the
    recurrence sees ``A∘M`` while the residual — and the stopping test —
    stay those of the original system; with ``M = None`` the applications
    reduce to the textbook method exactly.

    Breakdown detection rides the scalars the recurrence already computes:
    ``|ρ| ≤ tiny`` or ``|(r0, v)| ≤ tiny`` (the Lanczos/pivot breakdowns)
    or a zero ω with an unconverged residual (the stabilizer stall) trips
    ``BREAKDOWN`` — the standard cure is a restart from the current
    iterate, which the recovery ladder applies.
    """
    guard = guard or health.DEFAULT_GUARD
    if M is None:
        M = lambda v: v
    r = b - A(x0)
    r0 = r
    zero_v = jnp.zeros_like(b)
    rr = dot(r, r)
    # scalar recurrences carry the dot's accumulation dtype (fp64 adjoint
    # solves pass full-precision dots; the fp32 default is unchanged)
    one = jnp.asarray(1.0, rr.dtype)

    def cond(s):
        rr, i, g = s[7], s[8], s[9]
        return health.running(g) & (rr > tol * tol) & (i < maxiter)

    def body(s):
        x, r, p, v, rho, alpha, omega, rr, i, g = s
        rho_new = dot(r0, r)
        beta = (rho_new / _nonzero(rho)) * (alpha / _nonzero(omega))
        p = r + beta * (p - omega * v)
        ph = M(p)
        v = A(ph)
        r0v = dot(r0, v)
        alpha = rho_new / _nonzero(r0v)
        sv = r - alpha * v
        sh = M(sv)
        t = A(sh)
        tt = dot(t, t)
        # t == 0 means sv == 0 (converged mid-iteration): take omega = 0 so
        # the update degenerates to the stable half-step.
        omega = jnp.where(tt > 0.0, dot(t, sv) / _nonzero(tt), 0.0)
        x = x + alpha * ph + omega * sh
        r = sv - omega * t
        rr_new = dot(r, r)
        breakdown = (
            (jnp.abs(rho_new) <= health.BREAKDOWN_TINY)
            | (jnp.abs(r0v) <= health.BREAKDOWN_TINY)
            | ((omega == 0.0) & (rr_new > tol * tol))
        )
        g = health.guard_update(g, rr_new, breakdown=breakdown, config=guard)
        return (x, r, p, v, rho_new, alpha, omega, rr_new, i + 1, g)

    s0 = (x0, r, zero_v, zero_v, one, one, one, rr, 0, health.guard_init(rr))
    out = jax.lax.while_loop(cond, body, s0)
    x, rr, i, g = out[0], out[7], out[8], out[9]
    return x, i, jnp.sqrt(rr), health.classify(g, rr, tol * tol)


def stationary(
    step: Callable,
    rnorm2: Callable,
    x0,
    *,
    tol: float = 1e-6,
    maxiter: int = 100,
    ref2=None,
    guard: health.GuardConfig = None,
):
    """Fixed-point iteration ``x ← step(x)`` with a residual-norm stop.

    The outer driver for ``method="mg"``: ``step`` is one V/W-cycle and
    ``rnorm2(x)`` the squared fine-level residual norm (whose ``dot`` owns
    the all-reduce when sharded).  Returns ``(x, iterations, ‖r‖,
    outcome)`` like the Krylov methods, so ``SolveInfo`` reporting is
    uniform.

    The stop is *relative* — ``‖r‖ ≤ tol·√ref2`` with ``ref2`` the squared
    norm of the right-hand side (falling back to the entry residual) —
    because ``rnorm2`` is the true residual recomputed each cycle: an
    absolute fp32 criterion would stagnate at the rounding floor that
    Krylov methods sail past on their recurred (drifting) residuals, and a
    reference to the entry residual would over-demand at warm starts.  A
    zero reference (all-zero RHS) also falls back to the entry residual so
    the loop cannot spin to ``maxiter`` on a solved system.
    """
    guard = guard or health.DEFAULT_GUARD
    rr0 = rnorm2(x0)
    if ref2 is None:
        ref2 = rr0
    else:
        ref2 = jnp.where(ref2 > 0.0, ref2, rr0)

    def cond(s):
        x, rr, i, g = s
        return health.running(g) & (rr > tol * tol * ref2) & (i < maxiter)

    def body(s):
        x, rr, i, g = s
        x = step(x)
        rr = rnorm2(x)
        g = health.guard_update(g, rr, config=guard)
        return (x, rr, i + 1, g)

    x, rr, i, g = jax.lax.while_loop(
        cond, body, (x0, rr0, 0, health.guard_init(rr0))
    )
    return x, i, jnp.sqrt(rr), health.classify(g, rr, tol * tol * ref2)


def chebyshev(
    A: Callable,
    b,
    x0,
    lmin: float,
    lmax: float,
    *,
    iters: int = 500,
    dot: Callable = None,
    tol: float = 0.0,
):
    """Reduction-free Chebyshev iteration — zero collectives per iteration.

    ``lmin``/``lmax`` must bracket the spectrum of ``A`` (Gershgorin bounds
    from the lowered tap form, or user-supplied ``lambda_bounds``).  The
    optional ``dot`` is used ONLY for the final residual report (one
    reduction per solve, not per iteration) — sharded callers pass their
    ``psum``-owning dot so the reported norm is global, not one brick's.
    That same end-of-run residual classifies the outcome against ``tol``
    (with the default ``tol=0.0`` a finite completion reports MAXITER —
    "ran the budget" — which is the honest word for a fixed-count method).
    """
    theta = 0.5 * (lmax + lmin)
    delta = 0.5 * (lmax - lmin)
    sigma1 = theta / delta

    r = b - A(x0)
    d = r / theta
    x = x0 + d
    rho = 1.0 / sigma1

    def body(k, s):
        x, r, d, rho = s
        r = r - A(d)
        rho_new = 1.0 / (2.0 * sigma1 - rho)
        d = rho_new * rho * d + (2.0 * rho_new / delta) * r
        x = x + d
        return (x, r, d, rho_new)

    x, r, d, rho = jax.lax.fori_loop(0, iters, body, (x, r, d, rho))
    rr = jnp.sum(r * r, dtype=jnp.float32) if dot is None else dot(r, r)
    return x, iters, jnp.sqrt(rr), health.classify_fixed(rr, tol * tol)


# ---------------------------------------------------------------------------
# batched ensembles: per-member convergence masking
# ---------------------------------------------------------------------------
#
# The batched variants solve B independent systems stacked on a leading
# axis in ONE masked loop: ``A`` applies the operator to the whole
# (B, X, Y, Z) stack (the engine's batch-aware compiled step), ``dot``
# reduces per member to a (B,) vector, and every scalar recurrence runs
# elementwise over the batch.  The loop runs until the *slowest* member
# converges; members that finish early are **frozen bitwise** — all of
# their carried state is held with ``jnp.where(active, new, old)`` (never
# an arithmetic no-op like ``x + 0*p``, which is not bitwise-stable for
# signed zeros / inf lanes) — and each member's iteration count stops
# advancing the moment its own residual passes the tolerance.


def _bc(s, like):
    """Broadcast a (B,) per-member scalar over ``like``'s trailing axes."""
    return s[(...,) + (None,) * (like.ndim - 1)]


def cg_batched(
    A, dot, b, x0, *, tol: float = 1e-6, maxiter: int = 500,
    guard: health.GuardConfig = None,
):
    """Classic CG over a (B, ...) stack; ``dot`` must reduce to (B,).

    Returns ``(x, iterations, ‖r‖, outcomes)`` with per-member (B,)
    iteration counts, residual norms and outcome words.  A poisoned member
    (NaN residual) freezes immediately and reports ``NAN_RESIDUAL`` — it
    can no longer masquerade as converged — while healthy members run on
    bitwise-unperturbed (members never mix: dots reduce per member and the
    operator does not couple the batch axis).  No preconditioner: the only
    M the frontend builds (multigrid) is not batch-aware.
    """
    guard = guard or health.DEFAULT_GUARD
    r = b - A(x0)
    p = r
    rr = dot(r, r)
    it0 = jnp.zeros(rr.shape, jnp.int32)

    def cond(s):
        rr, i, g = s[3], s[5], s[6]
        return jnp.any((rr > tol * tol) & (g[0] == health.RUNNING)) & (i < maxiter)

    def body(s):
        x, r, p, rr, it, i, g = s
        active = (rr > tol * tol) & (g[0] == health.RUNNING)
        a4 = _bc(active, x)
        Ap = A(p)
        alpha = rr / _nonzero(dot(p, Ap))
        x = jnp.where(a4, x + _bc(alpha, x) * p, x)
        r_new = r - _bc(alpha, r) * Ap
        rr_new = dot(r_new, r_new)
        beta = rr_new / _nonzero(rr)
        p = jnp.where(a4, r_new + _bc(beta, p) * p, p)
        r = jnp.where(a4, r_new, r)
        g = health.guard_update(g, rr_new, where=active, config=guard)
        rr = jnp.where(active, rr_new, rr)
        return (x, r, p, rr, it + active.astype(jnp.int32), i + 1, g)

    s0 = (x0, r, p, rr, it0, jnp.asarray(0, jnp.int32), health.guard_init(rr))
    x, r, p, rr, it, _, g = jax.lax.while_loop(cond, body, s0)
    return x, it, jnp.sqrt(rr), health.classify(g, rr, tol * tol)


def pipecg_batched(
    A, dot2, b, x0, *, tol: float = 1e-6, maxiter: int = 500,
    guard: health.GuardConfig = None,
):
    """Pipelined CG over a (B, ...) stack; ``dot2`` reduces to two (B,)s.

    Same Ghysels–Vanroose recurrences as :func:`pipecg` run elementwise
    over the batch, including the periodic residual replacement (applied on
    the shared iteration clock, then masked so frozen members keep their
    converged state bitwise).  Per-member outcome words as in
    :func:`cg_batched`.
    """
    guard = guard or health.DEFAULT_GUARD
    r = b - A(x0)
    w_ = A(r)
    zero = jnp.zeros_like(b)
    rr0 = dot2(r, r, r, r)[0]  # (B,) true entry residuals
    replace_every = 25

    def body(s):
        x, r, w_, z, p, sv, rr, alpha_prev, it, i, fresh, g = s
        active = (rr > tol * tol) & (g[0] == health.RUNNING)
        a4 = _bc(active, x)
        gamma, delta = dot2(r, r, w_, r)
        n = A(w_)  # overlapped SpMV
        beta = jnp.where(fresh, 0.0, gamma / _nonzero(rr))
        denom = _nonzero(delta - beta * gamma / jnp.where(fresh, 1.0, alpha_prev))
        alpha = gamma / denom
        z_new = n + _bc(beta, z) * z
        p_new = r + _bc(beta, p) * p
        sv_new = w_ + _bc(beta, sv) * sv
        x = jnp.where(a4, x + _bc(alpha, x) * p_new, x)
        r_new = r - _bc(alpha, r) * sv_new
        w_new = w_ - _bc(alpha, w_) * z_new
        do = (i + 1) % replace_every == 0
        r_new, w_new = jax.lax.cond(
            do,
            lambda x, r_, w: (b - A(x), A(b - A(x))),
            lambda x, r_, w: (r_, w),
            x,
            r_new,
            w_new,
        )
        r = jnp.where(a4, r_new, r)
        w_ = jnp.where(a4, w_new, w_)
        z = jnp.where(a4, z_new, z)
        p = jnp.where(a4, p_new, p)
        sv = jnp.where(a4, sv_new, sv)
        # gamma is ‖r‖² *before* this update — the same one-iteration lag the
        # unbatched cond() has — so a member freezes one step after crossing
        g = health.guard_update(g, gamma, where=active, config=guard)
        rr = jnp.where(active, gamma, rr)
        alpha_prev = jnp.where(active, alpha, alpha_prev)
        return (x, r, w_, z, p, sv, rr, alpha_prev,
                it + active.astype(jnp.int32), i + 1, do, g)

    def cond(s):
        rr, i, g = s[6], s[9], s[11]
        return jnp.any((rr > tol * tol) & (g[0] == health.RUNNING)) & (i < maxiter)

    s0 = (
        x0,
        r,
        w_,
        zero,
        zero,
        zero,
        rr0,
        jnp.ones(rr0.shape, jnp.float32),
        jnp.zeros(rr0.shape, jnp.int32),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(True),
        health.guard_init(rr0),
    )
    out = jax.lax.while_loop(cond, body, s0)
    x, it, g = out[0], out[8], out[11]
    rr = dot2(out[1], out[1], out[1], out[1])[0]
    return x, it, jnp.sqrt(rr), health.classify(g, rr, tol * tol)


def bicgstab_batched(
    A, dot, b, x0, *, tol: float = 1e-6, maxiter: int = 500,
    guard: health.GuardConfig = None,
):
    """BiCGSTAB over a (B, ...) stack; ``dot`` must reduce to (B,).

    The ensemble workhorse: members may carry *different coefficients* (the
    operator reads per-member coefficient stacks), so each lane converges at
    its own rate and freezes independently.  Per-member outcome words as in
    :func:`cg_batched`, including per-member ρ/ω breakdown flags.
    """
    guard = guard or health.DEFAULT_GUARD
    r = b - A(x0)
    r0 = r
    rr = dot(r, r)
    ones = jnp.ones(rr.shape, jnp.float32)
    zero_v = jnp.zeros_like(b)

    def cond(s):
        rr, i, g = s[7], s[9], s[10]
        return jnp.any((rr > tol * tol) & (g[0] == health.RUNNING)) & (i < maxiter)

    def body(s):
        x, r, p, v, rho, alpha, omega, rr, it, i, g = s
        active = (rr > tol * tol) & (g[0] == health.RUNNING)
        a4 = _bc(active, x)
        rho_new = dot(r0, r)
        beta = (rho_new / _nonzero(rho)) * (alpha / _nonzero(omega))
        p_new = r + _bc(beta, p) * (p - _bc(omega, v) * v)
        v_new = A(p_new)
        r0v = dot(r0, v_new)
        alpha_new = rho_new / _nonzero(r0v)
        sv = r - _bc(alpha_new, r) * v_new
        t = A(sv)
        tt = dot(t, t)
        omega_new = jnp.where(tt > 0.0, dot(t, sv) / _nonzero(tt), 0.0)
        x = jnp.where(
            a4, x + _bc(alpha_new, x) * p_new + _bc(omega_new, x) * sv, x
        )
        r_new = sv - _bc(omega_new, sv) * t
        rr_new = dot(r_new, r_new)
        breakdown = (
            (jnp.abs(rho_new) <= health.BREAKDOWN_TINY)
            | (jnp.abs(r0v) <= health.BREAKDOWN_TINY)
            | ((omega_new == 0.0) & (rr_new > tol * tol))
        )
        r = jnp.where(a4, r_new, r)
        p = jnp.where(a4, p_new, p)
        v = jnp.where(a4, v_new, v)
        rho = jnp.where(active, rho_new, rho)
        alpha = jnp.where(active, alpha_new, alpha)
        omega = jnp.where(active, omega_new, omega)
        g = health.guard_update(
            g, rr_new, breakdown=breakdown, where=active, config=guard
        )
        rr = jnp.where(active, rr_new, rr)
        return (x, r, p, v, rho, alpha, omega, rr,
                it + active.astype(jnp.int32), i + 1, g)

    s0 = (x0, r, zero_v, zero_v, ones, ones, ones, rr,
          jnp.zeros(rr.shape, jnp.int32), jnp.asarray(0, jnp.int32),
          health.guard_init(rr))
    out = jax.lax.while_loop(cond, body, s0)
    g = out[10]
    return out[0], out[8], jnp.sqrt(out[7]), health.classify(g, out[7], tol * tol)


def jacobi(
    step: Callable,
    x0,
    *,
    iters: int = 500,
    rnorm2: Callable = None,
    tol: float = 0.0,
):
    """Reduction-free Jacobi relaxation: ``x ← step(x)`` for ``iters`` steps.

    ``step`` is the damped update ``x + D⁻¹(b − A x)`` (with the Moat pinned
    to ``b`` by the caller); for diagonally dominant operators it always
    converges — zero collectives per iteration and only one neighbour
    exchange, the cheapest member of the paper's "reduction-free implicit
    methods" family (Chebyshev converges faster per iteration).

    With ``rnorm2`` (squared true-residual norm, e.g. ``‖b − A x‖²`` with a
    ``psum``-owning dot when sharded) the end-of-run residual is reported
    and classified — one extra operator application per *solve*, not per
    iteration.  Without it the legacy contract holds (residual 0) and the
    outcome falls back to a finiteness check on the iterate itself, so a
    poisoned run still cannot masquerade as CONVERGED.
    """
    x = jax.lax.fori_loop(0, iters, lambda k, x: step(x), x0)
    if rnorm2 is not None:
        rr = rnorm2(x)
        return x, iters, jnp.sqrt(rr), health.classify_fixed(rr, tol * tol)
    finite = jnp.all(jnp.isfinite(x))
    outcome = jnp.where(
        finite, health.MAXITER, health.NAN_RESIDUAL
    ).astype(jnp.int32)
    return x, iters, jnp.zeros(()), outcome
