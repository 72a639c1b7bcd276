"""``wfa.solve`` — matrix-free implicit solves through the program compiler.

The explicit path records a program and lowers every loop body to one fused
Pallas kernel; this module does the same for *implicit* systems.  The
operator body recorded inside ``with Operator():`` (see
:mod:`repro.solver.frontend`) compiles through the engine's single backend
dispatch (:func:`repro.engine.compile_body` — the identical
IR-normalization → fused-codegen pipeline of :mod:`repro.compiler`) into one
``pallas_call`` per operator application — kernel cache, stats counters and
logged interpreter fallback included — and the matrix-free iterations of
:mod:`repro.solver.krylov` run on top of the compiled application.
``method="mg"`` / ``precondition="mg"`` add geometric multigrid
(:mod:`repro.solver.multigrid`): a compiled V/W-cycle hierarchy whose
iteration counts stay flat as grids grow.

Entry points:

* :func:`solve` — run a recorded system to convergence (also reachable as
  ``WFAInterface.solve``); ``mesh=`` composes with ``shard_map`` the same
  way ``backend="pallas"`` does for explicit programs (halo-pad brick →
  fused kernel, dot products as ONE fused ``psum`` over both mesh axes);
* :func:`make_solver` / :func:`make_sharded_solver` — build a reusable
  jitted step (benchmarks, time-stepping drivers);
* :func:`operator_fns` — just the compiled ``(A, rhs)`` applications (the
  legacy ``repro.core.implicit`` drivers are wired through this).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compiler import LoweringError, Tap, lower_group
from repro.core.program import Program, _group_ops, release_program
from repro.engine.stats import record_program, span
from repro.solver import health, krylov

log = logging.getLogger("repro.solver")

METHODS = ("cg", "pipecg", "bicgstab", "chebyshev", "jacobi", "mg")

#: methods that never touch a dot product — zero collectives per iteration
REDUCTION_FREE = ("chebyshev", "jacobi")

#: methods that accept ``precondition="mg"`` (CG needs an SPD M; BiCGSTAB
#: preconditions from the right, so any fixed linear M works)
PRECONDITIONABLE = ("cg", "bicgstab")


@dataclasses.dataclass
class SolveInfo:
    """Per-call convergence record returned by ``solve(..., return_info=True)``.

    On a batched solve (``options.batch = B > 1``) ``iterations``,
    ``residual`` and ``outcomes`` carry a trailing member axis — shape
    ``(steps, B)`` — with each member's own masked iteration count (see
    :mod:`repro.solver.krylov`'s batched variants).

    ``outcomes`` holds the :mod:`repro.solver.health` taxonomy name per
    time step (``CONVERGED`` / ``MAXITER`` / ``NAN_RESIDUAL`` /
    ``BREAKDOWN`` / ``STAGNATED`` / ``DIVERGED``); ``recovery`` is the
    :class:`~repro.solver.health.RecoveryTrace` when the solve went through
    the escalation ladder (None when the first attempt stood)."""

    method: str
    backend: str
    iterations: np.ndarray  # (steps,) inner iterations per time step
    residual: np.ndarray  # (steps,) final ‖r‖ per time step
    outcomes: Optional[np.ndarray] = None  # (steps,) taxonomy names
    recovery: Optional["health.RecoveryTrace"] = None


# ---------------------------------------------------------------------------
# program splitting + validation
# ---------------------------------------------------------------------------


def _answer_name(program: Program, answer) -> str:
    name = getattr(answer, "name", answer)
    if name not in program.fields:
        raise ValueError(f"answer field {name!r} is not registered in this program")
    return name


def _split(program: Program, answer: str):
    """-> ((op_loop, op_ops), (rhs_loop, rhs_ops) | None), validated."""
    op_groups, rhs_groups = [], []
    for loop, ops in _group_ops(program):
        role = getattr(loop, "role", None)
        if role == "operator":
            op_groups.append((loop, ops))
        elif role == "rhs":
            rhs_groups.append((loop, ops))
        else:
            raise ValueError(
                "wfa.solve programs may only contain Operator()/Rhs() "
                f"groups; found updates under {getattr(loop, 'name', loop)!r}"
            )
    if len(op_groups) != 1:
        raise ValueError(
            f"expected exactly one Operator() group, found {len(op_groups)}"
        )
    if len(rhs_groups) > 1:
        raise ValueError(f"expected at most one Rhs() group, found {len(rhs_groups)}")
    for _, ops in op_groups + rhs_groups:
        written = {op.field_name for op in ops}
        if written != {answer}:
            raise ValueError(
                "Operator()/Rhs() bodies must update only the unknown field "
                f"{answer!r}; they write {sorted(written)}"
            )
    return op_groups[0], (rhs_groups[0] if rhs_groups else None)


def _lower_operator(op_ops: Sequence, answer: str):
    """Lower the operator body for validation / bounds / diagonal extraction.

    Returns the :class:`LoweredGroup`, or ``None`` when the body is not
    affine-lowerable (the application then runs on the interpreter fallback
    and linearity cannot be checked statically).  Raises ``ValueError`` for
    bodies that lower but are *not linear* in the unknown — Krylov methods
    would silently diverge on those.
    """
    try:
        group = lower_group(op_ops)
    except LoweringError:
        return None
    for u in group.updates:
        if u.const != 0.0:
            raise ValueError(
                f"operator body has a constant term ({u.const}); A(x) must "
                "be linear in the unknown — move constants into the Rhs()"
            )
        for coeff, taps in u.terms:
            n_unknown = sum(t.field == answer for t in taps)
            if n_unknown == 0:
                raise ValueError(
                    "operator term reads only coefficient fields — an "
                    "affine shift; move it into the Rhs()"
                )
            if n_unknown > 1:
                raise ValueError(
                    "operator body is nonlinear in the unknown "
                    f"({n_unknown} taps of {answer!r} multiplied); Krylov "
                    "methods need a linear operator"
                )
    return group


def gershgorin_bounds(group, answer: str) -> Optional[Tuple[float, float]]:
    """Eigenvalue bounds of the lowered operator via Gershgorin circles.

    Only for constant-coefficient single-update bodies (every term one tap
    of the unknown): centre = diagonal coefficient, radius = Σ|off-diagonal|.
    The identity Moat rows contribute eigenvalue 1, so the bracket is widened
    to include it.  Returns ``None`` when bounds cannot be derived (variable
    coefficients) or the operator is indefinite — pass ``lambda_bounds=``.
    """
    if group is None or len(group.updates) != 1:
        return None
    diag = 0.0
    radius = 0.0
    for coeff, taps in group.updates[0].terms:
        if len(taps) != 1 or taps[0].field != answer:
            return None
        t = taps[0]
        if (t.dz, t.dx, t.dy) == (0, 0, 0):
            diag += coeff
        else:
            radius += abs(coeff)
    lmin = min(diag - radius, 1.0)
    lmax = max(diag + radius, 1.0)
    if lmin <= 0.0:
        return None
    return lmin, lmax


def _resolve_bounds(method, lambda_bounds, group, answer):
    if method != "chebyshev":
        return None
    bounds = lambda_bounds or gershgorin_bounds(group, answer)
    if bounds is None:
        raise ValueError(
            "chebyshev needs eigenvalue bounds: the operator does not admit "
            "automatic Gershgorin bounds — pass lambda_bounds=(lmin, lmax)"
        )
    return float(bounds[0]), float(bounds[1])


def _check_jacobi(method, group):
    if method == "jacobi" and (group is None or len(group.updates) != 1):
        raise ValueError(
            "jacobi needs a lowerable single-update affine operator (the "
            "diagonal is read off the tap form); use bicgstab instead"
        )


def _check_precondition(method, precondition):
    if precondition not in (None, "mg"):
        raise ValueError(
            f"unknown preconditioner {precondition!r}; expected None or 'mg'"
        )
    if precondition is not None and method not in PRECONDITIONABLE:
        hint = " (method='mg' is already multigrid)" if method == "mg" else ""
        raise ValueError(
            f"precondition='mg' supports methods {PRECONDITIONABLE}; "
            f"got method={method!r}{hint}"
        )


def _build_mg(method, precondition, group, name, shape, dtype, backend, mg_opts):
    """Build the multigrid hierarchy when ``method``/``precondition`` asks.

    ``method="mg"`` turns an illegal system (grid not coarsenable,
    non-affine / variable-coefficient / asymmetric operator) into a clear
    ``ValueError``; ``precondition="mg"`` degrades gracefully — a logged
    warning and a fallback to the unpreconditioned method.
    """
    if method != "mg" and precondition != "mg":
        return None
    from repro.solver.multigrid import build_multigrid

    try:
        return build_multigrid(group, name, shape, dtype, backend, mg_opts)
    except LoweringError as e:
        if method == "mg":
            raise ValueError(f"method='mg' cannot be built: {e}") from e
        log.warning(
            "precondition='mg' unavailable (%s) — falling back to "
            "unpreconditioned %s",
            e,
            method,
        )
        return None


def _jacobi_diag(group, answer: str, env):
    """Diagonal of the operator: a scalar, or an array for variable
    coefficients (center-tap products only)."""
    diag = None
    for coeff, taps in group.updates[0].terms:
        mine = [t for t in taps if t.field == answer]
        if mine != [Tap(answer, 0, 0, 0)]:
            continue  # off-diagonal term
        term = coeff
        for t in taps:
            if t.field == answer:
                continue
            if (t.dz, t.dx, t.dy) != (0, 0, 0):
                raise ValueError(
                    "jacobi: coefficient tap with nonzero offset is not "
                    "supported; use bicgstab"
                )
            term = term * env[t.field]
        diag = term if diag is None else diag + term
    if diag is None:
        raise ValueError("jacobi: operator has no diagonal (center) tap")
    return diag


def _written_mask(group, shape) -> np.ndarray:
    """(X, Y, Z) bool mask of cells the operator body writes (the rest are
    identity rows)."""
    nx, ny, nz = shape
    m = np.zeros((nx, ny, nz), dtype=bool)
    for u in group.updates:
        m[1:-1, 1:-1, u.z0 : u.z0 + u.zlen] = True
    return m


def _z_window(group, nz: int) -> np.ndarray:
    zw = np.zeros((1, 1, nz), dtype=bool)
    for u in group.updates:
        zw[0, 0, u.z0 : u.z0 + u.zlen] = True
    return zw


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------




def _make_runner(
    *,
    method: str,
    name: str,
    coef_names,
    op_step: Callable,
    rhs_step: Optional[Callable],
    dot: Callable,
    dot2: Callable,
    tol: float,
    maxiter: int,
    steps: int,
    bounds,
    group,
    jacobi_mask: Callable,
    mg=None,
    M: Optional[Callable] = None,
    batch: int = 1,
):
    """Shared solve driver: ``run(x0, *coefs) -> (x, (iters, res, outcomes))``.

    Both builders delegate here so the method dispatch and the per-step
    ``Rhs() → Krylov`` loop cannot diverge between the single-device and
    sharded paths; they differ only in the injected ``dot``/``dot2`` (the
    sharded ones own the ``psum``), ``jacobi_mask`` (static array vs traced
    from mesh coordinates inside ``shard_map``) and ``M`` (the sharded
    preconditioner gathers/slices around the cycle).  ``mg`` carries the
    compiled :class:`~repro.solver.multigrid.Multigrid` for
    ``method="mg"``; ``M`` is the preconditioner action for CG/BiCGSTAB.

    ``batch=B`` routes the Krylov methods to their per-member-masked
    batched variants (``dot``/``dot2`` then reduce to (B,) vectors) and
    broadcasts the reduction-free methods' shared iteration count to (B,),
    so ``(iters, res, outcomes)`` are uniformly per-member.  ``outcomes``
    is the per-step :mod:`repro.solver.health` taxonomy word.
    """

    def run_method(A, b, x0, envc):
        if method == "mg":
            return krylov.stationary(
                lambda x: mg.cycle(x, b),
                lambda x: mg.residual_norm2(x, b, dot),
                x0,
                tol=tol,
                maxiter=maxiter,
                ref2=dot(b, b),
            )
        if method == "cg":
            if batch > 1:
                return krylov.cg_batched(A, dot, b, x0, tol=tol, maxiter=maxiter)
            return krylov.cg(
                A, dot, b, x0, tol=tol, maxiter=maxiter, M=M, dot2=dot2
            )
        if method == "pipecg":
            if batch > 1:
                return krylov.pipecg_batched(
                    A, dot2, b, x0, tol=tol, maxiter=maxiter
                )
            return krylov.pipecg(A, dot2, b, x0, tol=tol, maxiter=maxiter)
        if method == "bicgstab":
            if batch > 1:
                return krylov.bicgstab_batched(
                    A, dot, b, x0, tol=tol, maxiter=maxiter
                )
            return krylov.bicgstab(A, dot, b, x0, tol=tol, maxiter=maxiter, M=M)
        if method == "chebyshev":
            return krylov.chebyshev(
                A, b, x0, bounds[0], bounds[1], iters=maxiter, dot=dot, tol=tol
            )
        D = _jacobi_diag(group, name, envc)
        mask = jacobi_mask()
        jstep = lambda x: jnp.where(mask, x + (b - A(x)) / D, b)
        # one extra operator application per solve reports + classifies the
        # true end-of-run residual (jacobi is otherwise reduction-free)
        return krylov.jacobi(
            jstep,
            x0,
            iters=maxiter,
            rnorm2=lambda x: dot(b - A(x), b - A(x)),
            tol=tol,
        )

    def run(x0, *coef_args):
        envc = dict(zip(coef_names, coef_args))

        def A(v):
            env = dict(envc)
            env[name] = v
            return op_step(env)[name]

        def one(x, _):
            if rhs_step is not None:
                env = dict(envc)
                env[name] = x
                b = rhs_step(env)[name]
            else:
                b = x
            x2, i, res, outcome = run_method(A, b, x, envc)
            if batch > 1:
                # fixed-count methods report one shared scalar; make every
                # method's (iters, res, outcome) per-member so SolveInfo is
                # uniform
                i = jnp.broadcast_to(jnp.asarray(i, jnp.int32), (batch,))
                res = jnp.broadcast_to(jnp.asarray(res, jnp.float32), (batch,))
                outcome = jnp.broadcast_to(
                    jnp.asarray(outcome, jnp.int32), (batch,)
                )
            return x2, (i, res, outcome)

        x2, aux = jax.lax.scan(one, x0, None, length=steps)
        return x2, aux

    return run


def _build_step(
    ops,
    loop,
    program: Program,
    backend: str,
    mesh_ctx=None,
    resident: int = 0,
    batch: int = 1,
) -> Callable:
    """One body application ``env -> env`` through the engine's single
    dispatch point (:func:`repro.engine.compile_body`): fused Pallas kernel
    when ``backend="pallas"`` (interpreter fallback on LoweringError,
    counted in ``repro.compiler.stats``), the shared roll interpreter
    otherwise; sharded when ``mesh_ctx`` is given.

    ``resident=K`` compiles the application against the engine's
    halo-resident layout (standing margin-``K`` buffers, in-place refresh +
    double-buffered outputs — :mod:`repro.engine.layout`).  The Krylov
    loops keep their vectors unpadded — each operator application is a
    single launch, so the pad it saves is bought back by interior
    re-slicing in every dot product — but the parameter keeps the solver
    on the same codegen surface as the explicit executors; the solve-loop
    allocations are instead eliminated by donating the jitted run's entry
    buffers (``donate_argnums``) and XLA's in-place ``while_loop``
    carries."""
    from repro.engine import compile_body

    if backend not in ("jit", "pallas"):
        raise ValueError(f"unknown solver backend {backend!r}")
    shapes = {n: f.shape for n, f in program.fields.items()}
    dtypes = {n: f.dtype for n, f in program.fields.items()}
    step, _ = compile_body(
        ops,
        loop,
        shapes,
        dtypes,
        backend,
        mesh_ctx=mesh_ctx,
        resident=resident,
        batch=batch,
    )
    return step


def operator_fns(program: Program, answer, backend: str = "jit"):
    """Compiled single-device ``(A, rhs)`` applications for a recorded system.

    ``A(v)`` applies the operator body with the unknown bound to ``v``
    (coefficient fields are closed over from their init data); ``rhs(T)``
    produces ``b`` from the state — the identity when no ``Rhs()`` group was
    recorded.  Both are jit-traceable.
    """
    name = _answer_name(program, answer)
    release_program(program)
    (op_loop, op_ops), rhs_group = _split(program, name)
    _lower_operator(op_ops, name)
    op_step = _build_step(op_ops, op_loop, program, backend)
    consts = {
        n: jnp.asarray(f.init_data)
        for n, f in program.fields.items()
        if n != name
    }

    def A(v):
        env = dict(consts)
        env[name] = v
        return op_step(env)[name]

    if rhs_group is None:
        return A, (lambda T: T)
    rhs_step = _build_step(rhs_group[1], rhs_group[0], program, backend)

    def rhs(T):
        env = dict(consts)
        env[name] = T
        return rhs_step(env)[name]

    return A, rhs


# ---------------------------------------------------------------------------
# single-device solver
# ---------------------------------------------------------------------------


def make_solver(
    program: Program,
    answer,
    *,
    method: str = "cg",
    backend: str = "pallas",
    tol: float = 1e-6,
    maxiter: int = 500,
    steps: int = 1,
    lambda_bounds: Optional[Tuple[float, float]] = None,
    precondition: Optional[str] = None,
    mg_opts=None,
    batch: int = 1,
    member_env=None,
    differentiable: bool = False,
) -> Callable:
    """Build a reusable jitted solver ``step_fn(x0) -> (x, (iters, res,
    outcomes))``.

    Each call advances ``steps`` implicit time steps: per step the ``Rhs()``
    body produces ``b`` from the state (identity if none was recorded) and
    the iteration solves ``A x = b`` warm-started at the state.
    ``method="mg"`` iterates geometric V/W-cycles; ``precondition="mg"``
    wraps one cycle from a zero guess around CG/BiCGSTAB (see
    :mod:`repro.solver.multigrid`; tune with ``mg_opts=MGOptions(...)``).

    ``batch=B`` builds an *ensemble* solver: ``step_fn`` takes and returns a
    ``(B, X, Y, Z)`` stack, the operator applies batch-aware (one compiled
    kernel launch per application for all members), dots reduce per member,
    and the Krylov loops freeze converged members while running to the
    slowest (see :mod:`repro.solver.krylov`).  ``member_env`` supplies
    per-member ``(B, X, Y, Z)`` stacks for coefficient fields (others
    broadcast from their init data); multigrid is not batch-aware, so
    ``method="mg"`` / ``precondition=`` require ``batch=1``.

    ``differentiable=True`` returns a solver that is reverse-mode
    differentiable via the implicit-function-theorem adjoint
    (:mod:`repro.solver.adjoint`): same ``step_fn(x0) -> (x, (iters, res,
    outcomes))`` contract, but traceable under ``jax.grad``/``jax.jit``,
    with nothing
    donated and dots accumulated in the field dtype.  Requires ``batch=1``
    and a Krylov/mg method; non-affine operator bodies raise instead of
    falling back to the interpreter.
    """
    if differentiable:
        if batch > 1:
            raise ValueError(
                "differentiable solves need batch=1 (vmap the returned "
                "solver for ensembles of gradients)"
            )
        from repro.solver.adjoint import make_differentiable_solver

        member_env = member_env or {}
        solve_fn = make_differentiable_solver(
            program,
            answer,
            method=method,
            backend="pallas" if backend is None else backend,
            tol=tol,
            maxiter=maxiter,
            steps=steps,
            precondition=precondition,
            mg_opts=mg_opts,
            return_info=True,
        )

        def step_fn(x0):
            coef = {
                n: member_env[n] for n in solve_fn.coef_names if n in member_env
            }
            return solve_fn(x0, coef)

        step_fn.symmetric_adjoint = solve_fn.symmetric_adjoint
        return step_fn
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; expected one of {METHODS}")
    _check_precondition(method, precondition)
    if batch > 1 and (method == "mg" or precondition is not None):
        raise ValueError(
            "batched solves support the pointwise/Krylov methods only; "
            "method='mg' and precondition= need batch=1 (the multigrid "
            "hierarchy is not batch-aware)"
        )
    name = _answer_name(program, answer)
    release_program(program)
    (op_loop, op_ops), rhs_group = _split(program, name)
    group = _lower_operator(op_ops, name)
    bounds = _resolve_bounds(method, lambda_bounds, group, name)
    _check_jacobi(method, group)
    field = program.fields[name]
    mg = _build_mg(
        method,
        precondition,
        group,
        name,
        field.shape,
        field.dtype,
        backend,
        mg_opts,
    )
    op_step = _build_step(op_ops, op_loop, program, backend, batch=batch)
    rhs_step = (
        _build_step(rhs_group[1], rhs_group[0], program, backend, batch=batch)
        if rhs_group is not None
        else None
    )
    member_env = member_env or {}
    coef_names = [n for n in program.fields if n != name]

    def _coef(n):
        v = jnp.asarray(member_env.get(n, program.fields[n].init_data))
        if batch > 1 and v.ndim == 3:
            v = jnp.broadcast_to(v, (batch,) + v.shape)
        return v

    coefs = [_coef(n) for n in coef_names]
    shape = program.fields[name].shape
    mask = jnp.asarray(_written_mask(group, shape)) if method == "jacobi" else None

    # fp32 accumulation matches the wafer reductions; the fp64 safe-mode
    # rung widens the operands, and its dots must widen with them or the
    # re-solve inherits the very overflow it is escaping
    # per-member reductions over the trailing (X, Y, Z) axes when batched
    axes = (1, 2, 3) if batch > 1 else None

    def dot(a, b):
        with jax.named_scope("wfa.krylov.dot"):
            acc = jnp.promote_types(a.dtype, jnp.float32)
            return jnp.sum(a * b, axis=axes, dtype=acc)

    def dot2(a, b, c, d):
        # XLA fuses the pair into one multi-output reduction (one sweep of
        # the shared operands; see repro.kernels.ops.dual_dot)
        return dot(a, b), dot(c, d)

    run = _make_runner(
        method=method,
        name=name,
        coef_names=coef_names,
        op_step=op_step,
        rhs_step=rhs_step,
        dot=dot,
        dot2=dot2,
        tol=tol,
        maxiter=maxiter,
        steps=steps,
        bounds=bounds,
        group=group,
        jacobi_mask=lambda: mask,
        mg=mg,
        M=mg.apply if (mg is not None and precondition == "mg") else None,
        batch=batch,
    )
    # donate the state: its buffer seeds the while_loop carry in place (the
    # rest of the iteration is already allocation-free — XLA aliases the
    # carry); step_fn hands in a buffer the caller never owned.
    jitted = jax.jit(run, donate_argnums=0)
    lead = (batch,) if batch > 1 else ()
    spec = jax.ShapeDtypeStruct(lead + tuple(shape), field.dtype)
    record_program(jitted, (spec, *coefs))

    def step_fn(x0):
        from repro.engine.executor import fresh_buffer

        with span("wfa.solver.dispatch"):
            with span("wfa.solver.copy_x0"):
                x0 = fresh_buffer(x0)
            return jitted(x0, *coefs)

    return step_fn


# ---------------------------------------------------------------------------
# sharded solver (shard_map + halo exchange + fused psum reductions)
# ---------------------------------------------------------------------------


def make_sharded_solver(
    program: Program,
    answer,
    mesh,
    *,
    method: str = "cg",
    backend: str = "pallas",
    tol: float = 1e-6,
    maxiter: int = 500,
    steps: int = 1,
    lambda_bounds: Optional[Tuple[float, float]] = None,
    precondition: Optional[str] = None,
    mg_opts=None,
):
    """Brick-sharded solver over ``mesh``; returns ``(step_fn, sharding)``.

    ``step_fn(x_global) -> (x, (iters, res, outcomes))`` runs the whole
    Krylov loop
    inside one ``shard_map``: operator applications halo-pad the brick
    (ICI ppermute) and run the fused kernel (``backend="pallas"``) or the
    roll interpreter per brick; dot products are one local pass plus ONE
    fused ``psum`` over both mesh axes.  Reduction-free methods (chebyshev,
    jacobi) run with zero collectives per iteration beyond the halo
    exchange.

    Multigrid coarsening halves extents, so below the fine level the grids
    stop dividing the mesh; the hierarchy therefore runs *gathered* — the
    classic all-coarse-levels-on-one-tile strategy, here one ``all_gather``
    per cycle and every device redundantly computing the (cheap) coarse
    work.  With ``precondition="mg"`` the fine-grid Krylov work (operator
    applications, fused-psum reductions) stays brick-sharded and only the
    preconditioner action gathers; with ``method="mg"`` the whole cycle
    iteration runs on the gathered field.
    """
    from repro.core.halo import local_moat_mask

    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; expected one of {METHODS}")
    _check_precondition(method, precondition)
    name = _answer_name(program, answer)
    release_program(program)
    (op_loop, op_ops), rhs_group = _split(program, name)
    group = _lower_operator(op_ops, name)
    bounds = _resolve_bounds(method, lambda_bounds, group, name)
    _check_jacobi(method, group)

    ax_x, ax_y = mesh.axis_names[-2], mesh.axis_names[-1]
    mx, my = mesh.shape[ax_x], mesh.shape[ax_y]
    shapes = {n: f.shape for n, f in program.fields.items()}
    for n, (nx, ny, _) in shapes.items():
        if nx % mx or ny % my:
            raise ValueError(
                f"field {n} shape ({nx},{ny}) not divisible by mesh ({mx},{my})"
            )
    nx, ny, nz = shapes[name]
    bx, by = nx // mx, ny // my

    field = program.fields[name]
    mg = _build_mg(
        method,
        precondition,
        group,
        name,
        field.shape,
        field.dtype,
        backend,
        mg_opts,
    )

    def _gather(v):
        g = jax.lax.all_gather(v, ax_x, axis=0, tiled=True)
        return jax.lax.all_gather(g, ax_y, axis=1, tiled=True)

    def _brick(g):
        cx = jax.lax.axis_index(ax_x) * bx
        cy = jax.lax.axis_index(ax_y) * by
        sizes = (bx, by) + tuple(g.shape[2:])
        return jax.lax.dynamic_slice(g, (cx, cy) + (0,) * (g.ndim - 2), sizes)

    mesh_ctx = None if method == "mg" else (mx, my, ax_x, ax_y)
    op_step = _build_step(op_ops, op_loop, program, backend, mesh_ctx=mesh_ctx)
    rhs_step = (
        _build_step(rhs_group[1], rhs_group[0], program, backend, mesh_ctx=mesh_ctx)
        if rhs_group is not None
        else None
    )
    zwin = _z_window(group, nz) if method == "jacobi" else None

    spec = jax.sharding.PartitionSpec(ax_x, ax_y, None)
    rspec = jax.sharding.PartitionSpec()
    sharding = jax.sharding.NamedSharding(mesh, spec)
    coef_names = [n for n in program.fields if n != name]
    coefs = [
        jax.device_put(jnp.asarray(program.fields[n].init_data), sharding)
        for n in coef_names
    ]

    def _local_dot(a, b):
        return jnp.sum(a * b, dtype=jnp.float32)

    def _psum_dot(a, b):
        # joint-axis psum: ONE all-reduce over the whole mesh instead of two
        # chained single-axis reductions (§Perf heat-implicit iteration 1)
        return jax.lax.psum(jnp.sum(a * b, dtype=jnp.float32), (ax_x, ax_y))

    def _local_dot2(a, b, c, d):
        return _local_dot(a, b), _local_dot(c, d)

    def _psum_dot2(a, b, c, d):
        from repro.kernels import ops as kops

        part = kops.dual_dot(a, b, c, d)  # one fused local pass
        part = jax.lax.psum(part, (ax_x, ax_y))  # ONE fused all-reduce
        return part[0], part[1]

    # method="mg" iterates on the gathered (replicated) field, so its
    # residual reduction is a plain local sum — identical on every device
    dot = _local_dot if method == "mg" else _psum_dot
    dot2 = _local_dot2 if method == "mg" else _psum_dot2
    M = None
    if mg is not None and precondition == "mg":
        M = lambda r: _brick(mg.apply(_gather(r)))

    run = _make_runner(
        method=method,
        name=name,
        coef_names=coef_names,
        op_step=op_step,
        rhs_step=rhs_step,
        dot=dot,
        dot2=dot2,
        tol=tol,
        maxiter=maxiter,
        steps=steps,
        bounds=bounds,
        group=group,
        jacobi_mask=lambda: (
            local_moat_mask(bx, by, ax_x, ax_y, mx, my) & jnp.asarray(zwin)
        ),
        mg=mg,
        M=M,
    )

    def _mg_local(x, *coef_args):
        out, aux = run(_gather(x), *[_gather(c) for c in coef_args])
        return _brick(out), aux

    local = _mg_local if method == "mg" else run

    from repro.core.jaxcompat import shard_map

    mapped = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(spec,) * (1 + len(coef_names)),
            out_specs=(spec, (rspec, rspec, rspec)),
            check=False,
        ),
        donate_argnums=0,  # the state buffer seeds the Krylov carry in place
    )

    def step_fn(x_global):
        from repro.engine.executor import fresh_buffer

        return mapped(jax.device_put(fresh_buffer(x_global), sharding), *coefs)

    return step_fn, sharding


# ---------------------------------------------------------------------------
# recovery ladder (bounded, logged escalation on failed solves)
# ---------------------------------------------------------------------------


def _cast_program(program: Program, dtype) -> Program:
    """Shallow dtype-cast view of a recorded program (fp64 safe mode).

    Ops reference fields by name, so sharing the op list with replica
    ``Field`` objects (same names/shapes, cast dtype + init data) is enough
    to rebuild every solver at the new precision.
    """
    import copy

    clone = Program.__new__(Program)
    clone.fields = {}
    clone.ops = program.ops
    clone._loop_stack = []
    for n, f in program.fields.items():
        f2 = copy.copy(f)
        f2.init_data = np.asarray(f.init_data, dtype)
        f2.dtype = f2.init_data.dtype
        clone.fields[n] = f2
    return clone


def _fetch4(step_fn, x0):
    """Run one solver attempt and land its 4 outputs on the host."""
    x, (iters, res, outs) = step_fn(x0)
    return (
        np.asarray(jax.device_get(x)),
        np.asarray(jax.device_get(iters)),
        np.asarray(jax.device_get(res)),
        np.asarray(jax.device_get(outs)),
    )


def _record_attempt(trace, method, dtype, outs, iters, res, reason):
    trace.record(
        method,
        np.dtype(dtype).name,
        health.outcome_name(health.worst(outs)),
        int(np.sum(iters)),
        float(np.asarray(res).ravel()[-1]),
        reason,
    )


def _recover_solve(program, name, first, x0, policy, kwargs, member_env):
    """Drive the escalation ladder after a failed first attempt.

    Rungs (each at most once, every attempt logged): same-method restart
    from the current iterate on BREAKDOWN (a fresh BiCGSTAB shadow residual
    is the textbook cure), cg/pipecg → bicgstab escalation, one fp64
    safe-mode re-solve.  Returns ``((x, iters, res, outs), trace)`` on
    success; raises :class:`~repro.solver.health.NumericalFault` carrying
    the populated trace when the ladder is exhausted.
    """
    from repro.engine.stats import stats as engine_stats

    method = kwargs["method"]
    dtype = program.fields[name].dtype
    trace = health.RecoveryTrace()
    x, iters, res, outs = first
    _record_attempt(trace, method, dtype, outs, iters, res, "initial")

    def failed(o):
        return health.any_failure(o, on_maxiter=policy.on_maxiter)

    def _attempt(kw, prog, start, reason, env=None, cast=None):
        nonlocal x, iters, res, outs
        engine_stats.recovery_attempts += 1
        solver = make_solver(
            prog, name, member_env=member_env if env is None else env, **kw
        )
        x, iters, res, outs = _fetch4(solver, start)
        if cast is not None:
            x = x.astype(cast)
        _record_attempt(
            trace, kw["method"], prog.fields[name].dtype, outs, iters, res, reason
        )
        log.warning("solve recovery: %s", trace.summary()[-1])
        return not failed(outs)

    # rung 1: restart from the current iterate (BREAKDOWN only)
    restarts = 0
    while (
        failed(outs)
        and health.worst(outs) == health.BREAKDOWN
        and restarts < policy.max_restarts
    ):
        restarts += 1
        if _attempt(kwargs, program, x, f"restart {restarts} after BREAKDOWN"):
            return (x, iters, res, outs), trace

    # rung 2: method escalation (symmetric methods → bicgstab)
    if failed(outs) and policy.escalate and method in ("cg", "pipecg"):
        why = health.outcome_name(health.worst(outs))
        kw2 = dict(kwargs, method="bicgstab", precondition=None)
        if _attempt(kw2, program, x0, f"escalate {method}->bicgstab after {why}"):
            return (x, iters, res, outs), trace

    # rung 3: one fp64 safe-mode re-solve of the original system (the
    # x64 context covers both build and run — tracing happens at call time).
    # Mosaic compiles no float64 kernel, so on a TPU the rung runs the
    # roll interpreter under XLA (backend="jit"), which takes float64.
    if failed(outs) and policy.safe_mode_fp64 and dtype != np.float64:
        from repro.kernels.ops import _interpret

        why = health.outcome_name(health.worst(outs))
        p64 = _cast_program(program, np.float64)
        env64 = {k: np.asarray(v, np.float64) for k, v in member_env.items()}
        kw64 = kwargs
        if kwargs["backend"] == "pallas" and not _interpret():
            kw64 = dict(kwargs, backend="jit")
        with jax.enable_x64(True):
            ok = _attempt(
                kw64,
                p64,
                np.asarray(x0, np.float64),
                f"fp64 safe mode after {why}",
                env=env64,
                cast=dtype,
            )
        if ok:
            return (x, iters, res, outs), trace

    engine_stats.numerical_faults += 1
    worst_name = health.outcome_name(health.worst(outs))
    # the taxonomy lands on stats even when the ladder is exhausted — a
    # fault must leave the same forensic trail a success does
    engine_stats.solve_outcomes = tuple(
        str(v) for v in np.unique(health.outcome_names(outs))
    )
    raise health.NumericalFault(
        f"solve({method}) failed with {worst_name} after "
        f"{len(trace.attempts)} attempt(s): {'; '.join(trace.summary())}",
        outcome=worst_name,
        trace=trace,
    )


# ---------------------------------------------------------------------------
# one-shot entry point (WFAInterface.solve lands here)
# ---------------------------------------------------------------------------


def solve(
    program: Program,
    answer,
    *,
    method: str = "cg",
    backend: Optional[str] = None,
    mesh=None,
    steps: int = 1,
    tol: float = 1e-6,
    maxiter: int = 500,
    lambda_bounds: Optional[Tuple[float, float]] = None,
    precondition: Optional[str] = None,
    mg_opts=None,
    return_info: bool = False,
    options=None,
    member_env=None,
):
    """Solve the recorded implicit system for ``answer``; returns the
    solution as a NumPy array (and a :class:`SolveInfo` when
    ``return_info=True``).

    Execution policy travels as ``options=RunOptions(...)`` — the legacy
    ``backend=`` / ``mesh=`` keywords are deprecation shims that warn once
    and forward (backend defaults to ``"pallas"``).  ``options.batch=B``
    solves a B-member ensemble in one masked Krylov loop: ``member_env``
    supplies per-member ``(B, X, Y, Z)`` stacks for the initial guess and/or
    coefficient fields (anything absent broadcasts from its init data), the
    returned solution is the ``(B, X, Y, Z)`` stack, converged members
    freeze bitwise while the loop runs to the slowest, and the per-member
    iteration counts land in ``SolveInfo.iterations`` (shape ``(steps, B)``)
    and ``repro.engine.stats.member_iterations``.

    The initial guess is the unknown field's init data (its Moat must carry
    the boundary values, as in the explicit path).  With ``mesh=`` the whole
    solve runs brick-sharded inside ``shard_map``.  ``method="mg"`` iterates
    geometric multigrid V/W-cycles; ``precondition="mg"`` accelerates
    CG/BiCGSTAB with one cycle per iteration — both keep iteration counts
    flat as the grid grows (see docs/solvers.md).

    ``options.differentiable=True`` routes through the
    implicit-function-theorem adjoint (:mod:`repro.solver.adjoint`): the
    eager result is numerically the same, and the underlying solver is
    reverse-mode differentiable — build it directly with
    ``make_solver(..., differentiable=True)`` (or
    :func:`repro.solver.adjoint.make_differentiable_solver`) to put
    ``jax.grad`` through the solve (see docs/adjoint.md).

    Example — the paper's BTCS heat system, multigrid-preconditioned::

        >>> import numpy as np
        >>> from repro.solver import record_btcs
        >>> T0 = np.full((17, 17, 9), 500.0, np.float32)
        >>> T0[1:-1, 1:-1, 0] = 300.0
        >>> wse, T = record_btcs(T0, 0.1)
        >>> x, info = wse.solve(T, method="cg", precondition="mg",
        ...                     backend="jit", tol=1e-6, return_info=True)
        >>> x.shape, bool(info.iterations[0] < 10)
        ((17, 17, 9), True)
    """
    from repro.engine.options import UNSET, resolve_options

    options = resolve_options(
        options,
        "wfa.solve",
        backend=UNSET if backend is None else backend,
        mesh=UNSET if mesh is None else mesh,
    )
    backend = options.resolved_backend("pallas")
    mesh = options.mesh
    batch = options.batch
    if mesh is not None and batch > 1:
        raise ValueError(
            "batched solves are single-device; drop mesh= or set batch=1"
        )
    if options.differentiable and mesh is not None:
        raise ValueError(
            "differentiable solves are single-device; drop mesh= (shard the "
            "forward solve only, or take gradients with mesh=None)"
        )
    name = _answer_name(program, answer)
    kwargs = dict(
        method=method,
        backend=backend,
        tol=tol,
        maxiter=maxiter,
        steps=steps,
        lambda_bounds=lambda_bounds,
        precondition=precondition,
        mg_opts=mg_opts,
    )
    member_env = member_env or {}
    if mesh is not None:
        step_fn, sharding = make_sharded_solver(program, name, mesh, **kwargs)
        x0 = jax.device_put(jnp.asarray(program.fields[name].init_data), sharding)
    else:
        step_fn = make_solver(
            program,
            name,
            batch=batch,
            member_env=member_env,
            differentiable=options.differentiable,
            **kwargs,
        )
        x0 = np.asarray(member_env.get(name, program.fields[name].init_data))
        if batch > 1 and x0.ndim == 3:
            x0 = np.broadcast_to(x0, (batch,) + x0.shape)
    x, iters, res, outs = _fetch4(step_fn, x0)
    trace = None
    recovery = options.recovery
    if recovery is not None and health.any_failure(
        outs, on_maxiter=recovery.on_maxiter
    ):
        if mesh is not None or batch > 1 or options.differentiable:
            # no escalation ladder off the plain path — still fail loud
            from repro.engine.stats import stats as engine_stats

            engine_stats.numerical_faults += 1
            trace = health.RecoveryTrace()
            _record_attempt(
                trace, method, program.fields[name].dtype, outs, iters, res,
                "initial",
            )
            worst_name = health.outcome_name(health.worst(outs))
            engine_stats.solve_outcomes = tuple(
                str(v) for v in np.unique(health.outcome_names(outs))
            )
            raise health.NumericalFault(
                f"solve({method}) failed with {worst_name} (no recovery "
                "ladder for sharded/batched/differentiable solves)",
                outcome=worst_name,
                trace=trace,
            )
        (x, iters, res, outs), trace = _recover_solve(
            program, name, (x, iters, res, outs), x0, recovery, kwargs, member_env
        )
    from repro.engine.stats import stats as engine_stats

    engine_stats.solve_outcomes = tuple(
        str(v) for v in np.unique(health.outcome_names(outs))
    )
    if batch > 1:
        engine_stats.ensemble_runs += 1
        engine_stats.ensemble_members += batch
        engine_stats.member_iterations = tuple(
            int(v) for v in iters.sum(axis=0)
        )
    if return_info:
        info = SolveInfo(
            method=method,
            backend=backend,
            iterations=iters,
            residual=res,
            outcomes=health.outcome_names(outs),
            recovery=trace,
        )
        return x, info
    return x
