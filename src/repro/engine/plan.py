"""The engine planner: one dispatch point for every execution path.

``plan(program, backend=..., mesh=..., time_tile=...)`` walks the recorded
program's op groups exactly once and schedules each as a :class:`Segment` —
either a *fused* segment (the :mod:`repro.compiler` pipeline built one
``pallas_call`` for the body, possibly time-tiled so k steps share one halo
exchange) or an *interpreter* segment (the shared roll-based step, used by
the ``numpy``/``jit`` backends and as the logged fallback for bodies that do
not lower).  :func:`repro.engine.executor.execute` then runs the plan on a
single device or inside ``shard_map`` — ``WFAInterface.make``,
``core.halo.run_sharded`` and the :mod:`repro.solver` step builders all
dispatch through here, so backend policy lives in exactly one place.

Time-tile selection: an explicit ``time_tile=k`` is honoured up to the
legality bounds of :func:`repro.compiler.ir.tile_group` (halo depth ``k·h``
must fit the brick, ``k`` the trip count) and clamped with a logged reason
otherwise; ``time_tile=None`` auto-picks the largest power-of-two divisor of
the trip count whose tiled halo stays small next to the brick
(:func:`repro.compiler.ir.auto_tile`), so auto-tiled runs never need a
remainder kernel.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Callable, List, Optional, Tuple

from repro.compiler import LoweringError, auto_tile, lower_group, tile_group
from repro.compiler.codegen import compile_group, compile_group_sharded, try_compile
from repro.core.halo import counted
from repro.core.program import Program, _group_ops, _interp_step
from repro.engine.options import UNSET, RunOptions, resolve_options
from repro.engine.stats import stats

log = logging.getLogger("repro.engine")
_LOGGED = set()


def _log_once(msg: str) -> None:
    """Log a degradation the plan takes on this device once per process;
    the counters in :data:`repro.engine.stats` count every occurrence."""
    if msg not in _LOGGED:
        _LOGGED.add(msg)
        log.warning("%s", msg)

#: user-facing backends accepted by plan() (``shard_map`` is ``jit`` + mesh)
BACKENDS = ("numpy", "jit", "shard_map", "pallas")


@dataclasses.dataclass
class Segment:
    """One scheduled op group: the loop, its ops, and the compiled step(s).

    ``step`` advances ``time_tile`` logical steps per call; ``step_rem``
    (untiled) covers the ``n % k`` remainder when the tile factor does not
    divide the trip count.  ``numpy`` plans carry no compiled steps — the
    executor interprets ``ops`` eagerly.
    """

    loop: Optional[object]
    ops: Tuple
    kind: str  # "fused" | "interp" | "eager"
    step: Optional[Callable] = None
    step_rem: Optional[Callable] = None
    time_tile: int = 1
    halo: int = 0
    reason: str = ""  # fallback / clamp explanation, "" when none
    #: boundary shell launches per tile when the segment runs the
    #: interior/boundary overlap split (0 = monolithic fused launch)
    split: int = 0

    @property
    def n_steps(self) -> int:
        return self.loop.n if self.loop is not None else 1


@dataclasses.dataclass
class ExecutionPlan:
    """Scheduled execution of one recorded program.

    ``layout`` is the halo-resident field layout the executor runs under
    (see :mod:`repro.engine.layout`): fused segments step on buffers padded
    once to the plan-wide margin ``layout.pad`` (= max ``k·h`` over the
    fused segments), with enter/exit conversions only at the program
    boundaries.  ``layout.pad == 0`` (interpreter plans, halo-free bodies,
    or ``resident=False``) degrades to the repacking path.
    """

    program: Program
    backend: str  # normalized: "numpy" | "jit" | "pallas"
    mesh: Optional[object]
    segments: List[Segment]
    layout: "HaloLayout" = None
    batch: int = 1  # leading ensemble axis every env buffer carries
    #: built for reverse-mode AD: runners must not donate entry buffers
    #: (they become VJP residuals) and the plan skips the halo-resident
    #: layout — see RunOptions.differentiable
    differentiable: bool = False

    @property
    def mesh_ctx(self) -> Optional[Tuple[int, int, str, str]]:
        return _mesh_ctx(self.mesh)


def _mesh_ctx(mesh) -> Optional[Tuple[int, int, str, str]]:
    """(mx, my, ax_x, ax_y) for the brick decomposition, None off-mesh."""
    if mesh is None:
        return None
    ax_x, ax_y = mesh.axis_names[-2], mesh.axis_names[-1]
    return mesh.shape[ax_x], mesh.shape[ax_y], ax_x, ax_y


def compile_body(
    ops,
    loop,
    shapes,
    dtypes,
    backend: str,
    *,
    mesh_ctx: Optional[Tuple[int, int, str, str]] = None,
    time_tile: int = 1,
    group=None,
    resident: int = 0,
    batch: int = 1,
    overlap: bool = False,
) -> Tuple[Callable, bool]:
    """Build one body application ``env -> env`` — THE backend dispatch.

    Returns ``(step, fused)``.  ``backend="pallas"`` routes through the
    compiler (fused kernel, ``time_tile`` sub-steps per call, interpreter
    fallback on :class:`LoweringError` counted in ``repro.compiler.stats``);
    ``backend="jit"`` returns the shared roll-interpreter step.  With
    ``mesh_ctx`` the step operates on per-device bricks inside ``shard_map``
    (ppermute halo exchange, the bytes one chip sends a step noted as the
    step's ``halo_bytes`` — :func:`repro.core.halo.counted`); without, on
    the global array.  Explicit program execution, ``run_sharded`` and the
    solver's operator/rhs applications all obtain their steps here.

    ``resident=K`` (fused paths only) makes the step operate on the
    halo-resident layout of :mod:`repro.engine.layout`: env buffers carry a
    standing margin ``K >= time_tile·h``, refreshed in place per launch,
    with kernel outputs written to fresh buffers of the same extent.
    Interpreter steps ignore it (the executor converts at segment
    boundaries).

    ``batch=B`` builds an ensemble step over ``(B, ...)``-stacked env
    buffers: fused kernels are vmapped over the leading axis below the
    refresh/barrier (see :func:`repro.compiler.codegen.compile_group`), and
    interpreter steps are vmapped whole — every jax primitive they use
    (rolls, where, dynamic updates, ppermute) carries a batching rule.

    ``overlap=True`` (fused resident paths) requests the interior/boundary
    kernel split so the margin exchange travels concurrently with the
    interior launch; illegal splits silently keep the monolithic kernel.
    """
    stats.bodies_compiled += 1
    if backend == "pallas":
        from repro.engine.hooks import fire_compile_hook
        from repro.kernels.ops import _interpret

        if mesh_ctx is None:

            def fn():
                # the hook can raise LoweringError — the injectable stand-in
                # for a real Mosaic compile failure; try_compile catches it
                # into the counted, logged interpreter fallback
                fire_compile_hook(getattr(loop, "name", None))
                return compile_group(
                    ops,
                    shapes,
                    dtypes,
                    interpret=_interpret(),
                    time_tile=time_tile,
                    group=group,
                    resident=resident,
                    batch=batch,
                    overlap=overlap,
                )

        else:
            mx, my, ax_x, ax_y = mesh_ctx

            def fn():
                fire_compile_hook(getattr(loop, "name", None))
                return compile_group_sharded(
                    ops,
                    shapes,
                    dtypes,
                    mesh_xy=(mx, my),
                    axis_names=(ax_x, ax_y),
                    interpret=_interpret(),
                    time_tile=time_tile,
                    group=group,
                    resident=resident,
                    batch=batch,
                    overlap=overlap,
                )

        step = try_compile(fn, loop)
        if step is not None:
            return (step if mesh_ctx is None else counted(step)), True
    elif backend != "jit":
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if mesh_ctx is None:
        base = _interp_step(ops)
    else:
        from repro.core.halo import interp_step_sharded

        mx, my, ax_x, ax_y = mesh_ctx
        base = interp_step_sharded(ops, ax_x, ax_y, mx, my)
    step = base
    if batch > 1:
        import jax

        step = lambda env: jax.vmap(base)(dict(env))  # noqa: E731
    return (step if mesh_ctx is None else counted(step, batch)), False


@dataclasses.dataclass
class LevelSegment:
    """One multigrid level's scheduled bodies and transfers.

    The multi-level analogue of :class:`Segment`: ``smooth`` and ``resid``
    are compiled body applications (``env -> env``, fused Pallas kernel or
    roll interpreter — the same :func:`compile_body` dispatch as every other
    path), ``restrict``/``prolong`` move arrays to/from the next-coarser
    level (``None`` on the coarsest).  ``diag`` is the level operator's
    constant diagonal, which the smoother and coarse solve divide by.
    """

    level: int
    shape: Tuple[int, int, int]
    smooth: Callable
    resid: Callable
    smooth_fused: bool
    resid_fused: bool
    diag: float
    restrict: Optional[Callable] = None
    prolong: Optional[Callable] = None


def transfer_kernels() -> bool:
    """Whether multigrid transfers run as Pallas kernels on this device.

    Mosaic restricts the transfer kernels' interleave reshapes (see
    :mod:`repro.kernels.transfer`), so on a TPU the pallas backend runs the
    jnp references instead — counted in ``stats.mg_transfer_refs`` and
    logged once — rather than crashing at first trace.
    """
    from repro.kernels.ops import _interpret

    return _interpret()


def plan_mg_levels(bodies, backend: str, dtype) -> List[LevelSegment]:
    """Schedule one multigrid hierarchy: every level body through the
    engine's single dispatch point, every transfer through the kernel cache.

    ``bodies`` is finest-first; each entry is a dict with ``shape``,
    ``diag`` and two recorded bodies ``smooth``/``resid`` as ``(ops,
    shapes, dtypes)`` triples (see :mod:`repro.solver.multigrid`, which
    records them per level).  ``backend="pallas"`` lowers each body to one
    fused kernel — one cache entry per level — and the transfers to the
    restriction/prolongation kernels of :mod:`repro.kernels.transfer`;
    ``backend="jit"`` uses the roll interpreter and the pure-jnp transfer
    references.  Per-level outcomes land in ``stats.mg_level_log``.
    """
    from repro.compiler.codegen import compile_transfer
    from repro.kernels.transfer import prolong_ref, restrict_ref

    segments: List[LevelSegment] = []
    log_entries = []
    for lvl, body in enumerate(bodies):
        shape = tuple(body["shape"])
        s_ops, s_shapes, s_dtypes = body["smooth"]
        r_ops, r_shapes, r_dtypes = body["resid"]
        smooth, s_fused = compile_body(s_ops, None, s_shapes, s_dtypes, backend)
        resid, r_fused = compile_body(r_ops, None, r_shapes, r_dtypes, backend)
        seg = LevelSegment(
            level=lvl,
            shape=shape,
            smooth=smooth,
            resid=resid,
            smooth_fused=s_fused,
            resid_fused=r_fused,
            diag=float(body["diag"]),
        )
        if lvl + 1 < len(bodies):
            coarse = tuple(bodies[lvl + 1]["shape"])
            use_kernels = False
            if backend == "pallas":
                use_kernels = transfer_kernels()
                if not use_kernels:
                    stats.mg_transfer_refs += 1
                    _log_once(
                        "multigrid transfers run as jnp references: Mosaic "
                        "cannot compile the transfer kernels' interleave "
                        "reshapes"
                    )
            if use_kernels:
                seg.restrict = compile_transfer(
                    "restrict", shape, coarse, dtype, interpret=True
                )
                seg.prolong = compile_transfer(
                    "prolong", shape, coarse, dtype, interpret=True
                )
            else:
                seg.restrict = restrict_ref
                seg.prolong = lambda c, n=shape: prolong_ref(c, n)
        segments.append(seg)
        log_entries.append((shape, s_fused, r_fused))
        stats.mg_levels_built += 1
    stats.mg_hierarchies += 1
    stats.mg_level_log = tuple(log_entries)
    return segments


def _brick_xy(program: Program, mesh_ctx, group) -> Tuple[int, int]:
    """Per-device brick extent of the fields ``group`` actually touches
    (the whole grid on a single device).  Anchored on the group's first
    written field — the same convention ``codegen._field_specs`` validates
    every fused field against — so tile legality is judged on the extent
    the kernel will really run over, not whichever field the program
    happened to declare first."""
    nx, ny, _ = program.fields[group.fields_written()[0]].shape
    if mesh_ctx is None:
        return nx, ny
    mx, my, _, _ = mesh_ctx
    return nx // mx, ny // my


def _pick_tile(
    group, loop, requested: Optional[int], brick_xy, cost=None, nz=None,
    fields=None,
) -> Tuple[int, str]:
    """Resolve the tile factor for one fused loop body: (k, clamp_reason).

    ``cost`` is this body's calibrated :class:`~repro.core.perfmodel.
    MeasuredCost` entry when one exists: auto selection then minimizes the
    measured model over the legal candidates instead of applying the static
    rule (``k = 1`` always admissible, so tiling cannot lose by
    construction — see :func:`repro.compiler.ir.auto_tile`).  ``fields``
    (``name -> (nz, dtype)``) lets both paths bound the kernel's window by
    its VMEM.
    """
    n = loop.n if loop is not None else 1
    if n <= 1:
        return 1, ""
    if requested is None:
        return auto_tile(group, brick_xy, n, cost=cost, nz=nz, fields=fields), ""
    k = max(1, int(requested))
    try:
        tile_group(group, k, brick_xy=brick_xy, n_steps=n, fields=fields)
        return k, ""
    except LoweringError as e:
        k_ok = min(k, n)
        while k_ok > 1:
            try:
                tile_group(group, k_ok, brick_xy=brick_xy, fields=fields)
                break
            except LoweringError:
                k_ok -= 1
        reason = f"time_tile={requested} clamped to k={k_ok}: {e}"
        log.warning("%s", reason)
        return k_ok, reason


def plan(
    program: Program,
    options=None,
    *,
    backend=UNSET,
    mesh=UNSET,
    time_tile=UNSET,
    resident=UNSET,
) -> ExecutionPlan:
    """Schedule a recorded program: group ops once, pick a strategy per body.

    Execution policy arrives as one frozen
    :class:`~repro.engine.options.RunOptions` bundle (a bare string is
    accepted as the backend, preserving the historical ``plan(program,
    "pallas")`` spelling).  The legacy ``backend=`` / ``mesh=`` /
    ``time_tile=`` / ``resident=`` keywords remain as deprecation shims that
    warn once per keyword and forward into the bundle.  ``options.batch=B``
    plans for ``(B, ...)``-stacked ensemble buffers: every compiled step is
    batch-aware and the plan records ``batch`` for the executor.

    Planning is two-pass so fields can be laid out *halo-resident*: pass one
    lowers every loop body and picks its tile factor, which fixes the
    run-wide margin ``K = max k·h``; pass two compiles each body against
    that layout (margin refresh in place + double-buffered kernel outputs —
    see :mod:`repro.engine.layout`).  ``resident=False`` forces the legacy
    repack-per-launch steps (the bitwise reference the residency tests
    compare against).
    """
    from repro.engine.layout import HaloLayout

    options = resolve_options(
        options,
        "engine.plan",
        backend=backend,
        mesh=mesh,
        time_tile=time_tile,
        resident=resident,
    )
    backend = options.resolved_backend("jit")
    mesh = options.mesh
    time_tile = options.time_tile
    resident = options.resident
    batch = options.batch
    overlap = options.overlap

    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if backend == "shard_map":
        backend = "jit"
        if mesh is None:
            from repro.core.halo import default_mesh2d

            mesh = default_mesh2d()
    if backend == "numpy":
        mesh = None  # the validation backend is eager + single-host
    mesh_ctx = _mesh_ctx(mesh)

    shapes = {n: f.shape for n, f in program.fields.items()}
    dtypes = {n: f.dtype for n, f in program.fields.items()}
    if mesh_ctx is not None:
        mx, my, _, _ = mesh_ctx
        for n, (nx, ny, _) in shapes.items():
            if nx % mx or ny % my:
                raise ValueError(
                    f"field {n} shape ({nx},{ny}) not divisible by mesh ({mx},{my})"
                )

    # pass one: lower + pick tile factors; the margin K is their max window
    scheduled = []
    for loop, ops in _group_ops(program):
        group = None
        k, reason = 1, ""
        cost = None
        if backend == "pallas":
            try:
                group = lower_group(ops)
            except LoweringError:
                group = None  # compile_body repeats the lowering to log/count
            if group is not None:
                from repro.core import perfmodel

                name0 = group.fields_written()[0]
                cost = perfmodel.cost_model.lookup(
                    group, shapes[name0][2], dtypes[name0]
                )
                if cost is not None:
                    stats.cost_model_hits += 1
                names = dict.fromkeys(group.fields_written() + group.fields_read())
                k, reason = _pick_tile(
                    group,
                    loop,
                    time_tile,
                    _brick_xy(program, mesh_ctx, group),
                    cost=cost,
                    nz=shapes[name0][2],
                    fields={n: (shapes[n][2], dtypes[n]) for n in names},
                )
        elif backend != "numpy" and time_tile is not None and time_tile != 1:
            # an explicit tile request on an interpreter backend is dropped,
            # not honoured — say so instead of silently running untiled
            reason = (
                f"time_tile={time_tile} ignored: backend {backend!r} has no "
                "fused kernels to tile (use backend='pallas')"
            )
            log.warning("%s", reason)
        scheduled.append((loop, ops, group, k, reason, cost))
    from repro.kernels.ops import _interpret

    pad = 0
    if resident and backend == "pallas" and not options.differentiable:
        # a differentiable plan keeps the repacking steps: the resident
        # protocol's margin rewrites and donated buffers are exactly the
        # buffer reuse a reverse pass cannot tolerate — saved residuals
        # must survive the forward sweep
        # monolithic resident launches double-buffer (each reads one buffer
        # and writes another), so they are safe on Mosaic, where the grid
        # runs its blocks in sequence over HBM, on one device and on a mesh
        pad = max(
            (k * g.halo for _, _, g, k, _, _ in scheduled if g is not None),
            default=0,
        )
    layout = HaloLayout(pad=pad, shapes=shapes)

    # pass two: compile each body against the layout
    segments: List[Segment] = []
    for loop, ops, group, k, reason, cost in scheduled:
        if backend == "numpy":
            segments.append(Segment(loop=loop, ops=tuple(ops), kind="eager"))
            continue
        # overlap decision: split the launch only where legal (resident
        # layout, nonempty interior at depth k·h) and wanted — forced by
        # overlap=True, or, on "auto", predicted faster by this body's
        # calibrated cost-model entry (no entry → keep today's schedule).
        # The split's region launches write in place, which is safe only
        # where blocks are evaluated functionally (interpret mode): on
        # Mosaic a block's halo window would read rows an earlier block of
        # the same launch already stepped.
        use_split = 0
        if group is not None and pad > 0 and group.halo > 0 and _interpret():
            from repro.compiler.ir import split_regions

            sp = split_regions(group, k, _brick_xy(program, mesh_ctx, group))
            if sp is not None and overlap is not False:
                if overlap is True:
                    use_split = len(sp.shells)
                elif cost is not None:
                    from repro.core.perfmodel import predict_step_us

                    name0 = group.fields_written()[0]
                    bxy = _brick_xy(program, mesh_ctx, group)
                    nz = shapes[name0][2]
                    t_fused = predict_step_us(cost, bxy, nz, group.halo, k)
                    t_split = predict_step_us(cost, bxy, nz, group.halo, k, split=True)
                    if t_split < t_fused:
                        use_split = len(sp.shells)
        step, fused = compile_body(
            ops,
            loop,
            shapes,
            dtypes,
            backend,
            mesh_ctx=mesh_ctx,
            time_tile=k,
            group=group,
            resident=pad,
            batch=batch,
            overlap=bool(use_split),
        )
        if not fused:
            k = 1
            use_split = 0
        seg = Segment(
            loop=loop,
            ops=tuple(ops),
            kind="fused" if fused else "interp",
            step=step,
            time_tile=k,
            halo=group.halo if group is not None else 0,
            reason=reason,
            split=use_split,
        )
        if fused and k > 1 and seg.n_steps % k:
            seg.step_rem, _ = compile_body(
                ops,
                loop,
                shapes,
                dtypes,
                backend,
                mesh_ctx=mesh_ctx,
                time_tile=1,
                group=group,
                resident=pad,
                batch=batch,
                overlap=bool(use_split),
            )
        if reason:
            stats.note_tile_reason(reason)
        if fused:
            stats.segments_fused += 1
        else:
            stats.segments_interp += 1
        segments.append(seg)

    stats.plans_built += 1
    stats.max_time_tile = max(
        stats.max_time_tile, max((s.time_tile for s in segments), default=1)
    )
    return ExecutionPlan(
        program=program,
        backend=backend,
        mesh=mesh,
        segments=segments,
        layout=layout,
        batch=batch,
        differentiable=options.differentiable,
    )
