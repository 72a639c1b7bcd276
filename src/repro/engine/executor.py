"""The engine executor: run an :class:`~repro.engine.plan.ExecutionPlan`.

One executor serves every backend the planner schedules:

* ``numpy`` — eager segment interpretation (the WFA validation mode);
* single device — segments wrapped in ``lax.fori_loop`` under one ``jax.jit``;
* mesh — the same loop structure applied per brick inside one ``shard_map``
  (ppermute halo exchange in each segment's step).

Time-tiled segments advance ``k`` steps per iteration (``n // k`` tiled
launches + ``n % k`` untiled remainder launches), which is where the
communication amortization lands: one halo exchange (or wrap pad) per tile.

Halo residency (:mod:`repro.engine.layout`): when the plan carries a padded
layout, the traced run *enters* it once (pad every field to the resident
extent), steps the fused segments on those standing buffers — margin slabs
refreshed in place, each launch reading one buffer and writing another
(double-buffered) — and *exits* once at the end; interpreter segments
inside a mixed plan are bracketed by exit/enter so their roll semantics see
plain arrays.  Launches run in pairs per loop iteration
(:func:`run_launches`), so the two buffers of each written field trade
places without a copy.  Both jitted executors **donate** their entry
buffers (``donate_argnums``), so with an all-fused plan the whole step
loop runs without repacking a single field.
The executor also derives the engine's static communication accounting from
the plan (see :mod:`repro.engine.stats`).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.program import _apply_op
from repro.engine.hooks import fire_step_hook
from repro.engine.plan import ExecutionPlan, Segment
from repro.engine.stats import record_program, span, stats


def run_launches(step, n: int, env):
    """Trace ``n`` launches of ``step``: pairs inside one ``fori_loop``, an
    odd one after it.

    A resident launch writes a fresh buffer, so its input and output are
    live at once; with one launch per iteration XLA must copy the output
    into the loop-carried buffer.  With two, the second output takes the
    buffer of the first input, which is dead after the first launch, and
    the loop runs copy-free.  Every step loop of the engine and the service
    goes through here.
    """
    if n >= 2:
        env = jax.lax.fori_loop(0, n // 2, lambda i, e: step(step(e)), env)
    if n % 2:
        env = step(env)
    return env


def step_segment(seg: Segment, n: int, env):
    """Trace ``n`` logical steps of ``seg``: ``n // k`` tiled launches, then
    ``n % k`` untiled ones (``k`` is 1 off the fused path)."""
    k = seg.time_tile
    env = run_launches(seg.step, n // k, env)
    return run_launches(seg.step_rem, n % k, env)


def _apply_segment(seg: Segment, env):
    """Trace one segment: its whole loop, or one application without one."""
    if seg.loop is None:
        return seg.step(env)
    return step_segment(seg, seg.loop.n, env)


def _layout_schedule(plan: ExecutionPlan):
    """The plan's step/conversion event stream: ``"enter"``/``"exit"``
    markers interleaved with segments.  Fused segments run on the layout's
    padded buffers; interpreter segments (mixed plans, lowering fallbacks)
    are bracketed by exit/enter so both step kinds see the env form they
    were compiled for.  With an all-fused plan this is exactly one enter
    and one exit per run.  Both the tracer and the repack accounting
    consume this one stream, so they cannot drift apart.
    """
    padded = False
    for seg in plan.segments:
        if seg.kind == "fused":
            if not padded:
                yield "enter"
                padded = True
        elif padded:
            yield "exit"
            padded = False
        yield seg
    if padded:
        yield "exit"


def _trace_plan(plan: ExecutionPlan, env):
    """Trace the whole plan: resident fused segments, plain interp segments
    (see :func:`_layout_schedule` for the conversion bracketing)."""
    layout = plan.layout
    if layout is None or layout.pad == 0:
        for seg in plan.segments:
            env = _apply_segment(seg, env)
        return env
    for ev in _layout_schedule(plan):
        if isinstance(ev, str):
            with jax.named_scope("wfa.engine.layout"):
                env = layout.enter(env) if ev == "enter" else layout.exit(env)
        else:
            env = _apply_segment(ev, env)
    return env


def fresh_buffer(v):
    """Device array safe to donate: never aliases a caller-owned buffer.

    Copies unconditionally: ``jnp.asarray`` is a no-op for device arrays,
    and on CPU backends it may *zero-copy* an aligned host numpy array —
    either way the jitted runners would donate (invalidate, then reuse)
    memory the caller still holds."""
    return jnp.array(v, copy=True)


def _account(plan: ExecutionPlan) -> None:
    """Static communication accounting for one execution of ``plan``.

    Fused segments pay one pad/exchange per kernel launch (none when the
    body is halo-free); interpreter segments pad per op, per step.  Single-
    device ``jit``/``numpy`` interpretation rolls in place — no pad events.
    On a resident plan the fused "exchange" is the in-place margin-slab
    refresh (same count, a fraction of the bytes) and the only repacking
    conversions are the layout enter/exit events — two for an all-fused
    plan, plus a pair around each interpreter segment in a mixed plan.
    """
    resident = (
        plan.layout is not None
        and plan.layout.pad > 0
        and any(seg.kind == "fused" for seg in plan.segments)
    )
    if resident:
        stats.resident_runs += 1
        stats.repacks += sum(
            1 for ev in _layout_schedule(plan) if isinstance(ev, str)
        )
    if plan.batch > 1:
        stats.ensemble_runs += 1
        stats.ensemble_members += plan.batch
    for seg in plan.segments:
        n, k = seg.n_steps, seg.time_tile
        stats.steps_run += n
        if seg.kind == "fused":
            tiled = n // k if k > 1 else 0
            launches = tiled + (n % k if k > 1 else n)
            stats.launches += launches
            stats.tiles_fused += tiled
            if seg.split:
                # overlap split: every launch event is one interior kernel
                # plus `split` boundary shells, its exchange slabs in
                # flight while the interior computes
                stats.interior_launches += launches
                stats.boundary_launches += launches * seg.split
                if seg.halo > 0:
                    stats.overlapped_exchanges += launches
            if seg.halo > 0:
                stats.exchanges += launches
                if not resident:
                    stats.repacks += launches  # full pad/concat per launch
        else:
            stats.launches += n
            if plan.mesh is not None:
                stats.exchanges += n * len(seg.ops)
                stats.repacks += n * len(seg.ops)
        if plan.mesh is not None:
            # noted on the mesh steps when they were traced
            stats.halo_bytes += (
                n // k * getattr(seg.step, "halo_bytes", 0.0)
                + n % k * getattr(seg.step_rem, "halo_bytes", 0.0)
            )


def _run_numpy(plan: ExecutionPlan, env: Dict[str, np.ndarray], check: int = 0):
    if plan.batch > 1:
        # the eager validation backend has no vectorizing machinery to
        # batch through — run the members one by one and restack
        outs = [
            _run_numpy_one(plan, {k: v[b] for k, v in env.items()}, check)
            for b in range(plan.batch)
        ]
        out = {k: np.stack([o[k] for o in outs]) for k in env}
    else:
        out = _run_numpy_one(plan, env, check)
    _account(plan)
    return out


def _run_numpy_one(plan: ExecutionPlan, env: Dict[str, np.ndarray], check=0):
    from repro.engine import health as ehealth

    env = {k: np.asarray(v).copy() for k, v in env.items()}
    roll = lambda a, s, ax: np.roll(a, s, axis=ax)  # noqa: E731
    step_idx, since, last_good, good_step = 0, 0, None, 0
    if check > 0:
        if not ehealth.probe(env):
            _sentinel_fault(env, 0, None, 0)
        last_good = {k: v.copy() for k, v in env.items()}
    for seg in plan.segments:
        for _ in range(seg.n_steps):
            for op in seg.ops:
                env[op.field_name] = _apply_op(op, env, np, roll)
            step_idx += 1
            since += 1
            if check > 0 and since >= check:
                since = 0
                if not ehealth.probe(env):
                    _sentinel_fault(env, step_idx, last_good, good_step)
                last_good = {k: v.copy() for k, v in env.items()}
                good_step = step_idx
    if check > 0 and since:
        if not ehealth.probe(env):
            _sentinel_fault(env, step_idx, last_good, good_step)
    return env


def _arg_spec(plan: ExecutionPlan):
    """The env a runner of ``plan`` takes, as shapes: every program field,
    with the ensemble axis in front on a batched plan."""
    lead = (plan.batch,) if plan.batch > 1 else ()
    return {
        n: jax.ShapeDtypeStruct(lead + tuple(f.shape), f.dtype)
        for n, f in plan.program.fields.items()
    }


def single_runner(plan: ExecutionPlan):
    """The jitted single-device runner for ``plan`` (entry env donated).

    Exposed for the residency tests: ``runner.lower(env)`` shows the
    donation markers and ``runner(env)`` consumes its argument buffers.
    Each call is one ``wfa.engine.dispatch`` span and adds the plan's
    counts to :data:`repro.engine.stats`; the jitted program is recorded
    for :func:`repro.engine.stats.device_scopes`.

    A :class:`~repro.engine.plan.ExecutionPlan` built with
    ``RunOptions(differentiable=True)`` is **not** donated: under AD the
    entry buffers become saved residuals of the reverse pass (and the
    caller's arrays must survive the call), so donation is suppressed —
    the documented donation/AD rule.
    """

    def run(env):
        return _trace_plan(plan, env)

    donate = () if plan.differentiable else (0,)
    return _dispatching(plan, jax.jit(run, donate_argnums=donate), _arg_spec(plan))


def _dispatching(plan: ExecutionPlan, jitted, spec):
    """The runner around ``jitted``: each call one ``wfa.engine.dispatch``
    span that adds the plan's counts to :data:`repro.engine.stats`, with
    ``lower``; the program is recorded, on the env ``spec``, for
    :func:`repro.engine.stats.device_scopes`."""
    record_program(jitted, (spec,))

    def runner(env):
        with span("wfa.engine.dispatch"):
            out = jitted(env)
            _account(plan)
        return out

    runner.lower = jitted.lower
    return runner


def _run_single(plan: ExecutionPlan, env):
    env = {k: fresh_buffer(v) for k, v in env.items()}
    return jax.device_get(single_runner(plan)(env))


def sharded_runner(plan: ExecutionPlan, names=None):
    """The jitted ``shard_map`` runner for ``plan`` (entry env donated).

    Returns ``(runner, sharding)``; the layout enter/exit happens *inside*
    the mapped function, so resident buffers are per-brick and the margin
    refresh is pure neighbour ppermute.  As with :func:`single_runner`,
    each call is one ``wfa.engine.dispatch`` span and adds the plan's
    counts (``halo_bytes`` among them) to :data:`repro.engine.stats`, the
    jitted program is recorded for
    :func:`repro.engine.stats.device_scopes`, and ``runner.lower`` lowers
    it.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.jaxcompat import shard_map

    mesh = plan.mesh
    _, _, ax_x, ax_y = plan.mesh_ctx
    # batched plans brick the trailing (X, Y) axes only: every device holds
    # all B members of its brick, so ensemble steps need no extra collectives
    spec = P(None, ax_x, ax_y, None) if plan.batch > 1 else P(ax_x, ax_y, None)
    sharding = jax.sharding.NamedSharding(mesh, spec)
    specs = {k: spec for k in (plan.program.fields if names is None else names)}

    def local(env):
        return _trace_plan(plan, env)

    stepped = jax.jit(
        shard_map(local, mesh=mesh, in_specs=(specs,), out_specs=specs, check=False),
        donate_argnums=() if plan.differentiable else (0,),
    )
    arg = _arg_spec(plan)
    shapes = {
        k: jax.ShapeDtypeStruct(arg[k].shape, arg[k].dtype, sharding=sharding)
        for k in specs
    }
    return _dispatching(plan, stepped, shapes), sharding


def _run_sharded(plan: ExecutionPlan, env):
    runner, sharding = sharded_runner(plan, names=list(env))
    genv = {k: jax.device_put(fresh_buffer(v), sharding) for k, v in env.items()}
    out = runner(genv)
    return {k: np.asarray(jax.device_get(v)) for k, v in out.items()}


# ---------------------------------------------------------------------------
# explicit-path sentinels: chunked guarded execution (RunOptions.check_finite)
# ---------------------------------------------------------------------------


def _sentinel_fault(env, step_idx, last_good, good_step, exit_fn=None):
    """Raise the NumericalFault for a tripped explicit-path probe."""
    from repro.engine import health as ehealth

    stats.numerical_faults += 1
    if exit_fn is not None:
        # resident state: look only at the interiors, margins are transient
        env = exit_fn(env)
    bad = ehealth.poisoned_fields(env)
    if last_good is not None and exit_fn is not None:
        last_good = exit_fn(last_good)
    if last_good is not None:
        last_good = {k: np.asarray(jax.device_get(v)) for k, v in last_good.items()}
    raise ehealth.NumericalFault(
        f"non-finite field state at step {step_idx} "
        f"(fields: {', '.join(bad) or 'unknown'}; "
        f"last finite probe at step {good_step})",
        outcome="NAN_RESIDUAL",
        step=step_idx,
        last_good=last_good,
    )


def _guarded_wrap(plan: ExecutionPlan, fn, names):
    """``jit(fn)`` for a single-device plan, ``jit(shard_map(fn))`` on a
    mesh — the guarded analogue of :func:`single_runner` /
    :func:`sharded_runner`, never donating (the previous chunk's env is the
    sentinel's ``last_good`` state and must survive the next launch)."""
    if plan.mesh is None:
        return jax.jit(fn)
    from jax.sharding import PartitionSpec as P

    from repro.core.jaxcompat import shard_map

    _, _, ax_x, ax_y = plan.mesh_ctx
    spec = P(None, ax_x, ax_y, None) if plan.batch > 1 else P(ax_x, ax_y, None)
    specs = {k: spec for k in names}
    return jax.jit(
        shard_map(fn, mesh=plan.mesh, in_specs=(specs,), out_specs=specs, check=False)
    )


def _guarded_loop_wrap(plan: ExecutionPlan, step_fn, per_chunk, names, pad=0):
    """One jitted guarded loop: up to ``nchunks`` iterations of
    ``per_chunk`` launches each, with the ``isfinite`` probe fused into the
    ``while_loop`` carry — a single dispatch per segment, stopping at the
    first failed probe.  ``pad`` is the resident margin of the env the loop
    steps (0 for plain arrays); the probe reads only the interiors, since a
    resident buffer's margins are transient.

    Returns a runner ``(env, nchunks) -> (env, chunks_run, ok)``.  The
    carry holds only the current state: keeping a last-good snapshot alive
    would block XLA from ping-ponging the chunk buffers in place and cost
    an extra generation per probe, so the happy path pays one reduction per
    chunk and nothing else.  ``nchunks`` is traced, which lets the caller
    reuse the same compiled runner to replay the prefix and regenerate the
    last probed-good state on the rare failure path.  On a mesh the
    per-brick verdicts reduce with one ``pmin`` inside the loop, so the
    stop condition is uniform across devices.
    """
    from repro.engine import health as ehealth

    mesh = plan.mesh

    def chunk(e):
        return run_launches(step_fn, per_chunk, e)

    def probe(out):
        ok = ehealth.probe_ok(out, pad)
        if mesh is None:
            return ok
        _, _, ax_x, ax_y = plan.mesh_ctx
        return jax.lax.pmin(ok.astype(jnp.int32), (ax_x, ax_y)) > 0

    def run(env, nchunks):
        def body(c):
            e, i, ok = c
            new = chunk(e)
            return (new, i + 1, probe(new))

        def cond(c):
            return c[2] & (c[1] < nchunks)

        init = (env, jnp.int32(0), jnp.bool_(True))
        return jax.lax.while_loop(cond, body, init)

    if mesh is None:
        return jax.jit(run)
    from jax.sharding import PartitionSpec as P

    from repro.core.jaxcompat import shard_map

    _, _, ax_x, ax_y = plan.mesh_ctx
    spec = P(None, ax_x, ax_y, None) if plan.batch > 1 else P(ax_x, ax_y, None)
    specs = {k: spec for k in names}
    return jax.jit(
        shard_map(
            run,
            mesh=mesh,
            in_specs=(specs, P()),
            out_specs=(specs, P(), P()),
            check=False,
        )
    )


def _run_guarded(plan: ExecutionPlan, env, every: int):
    """Chunked execution probing field finiteness every ~``every`` steps.

    The plan's compiled launches are regrouped into chunks of
    ``ceil(every / k)`` launches, and each segment runs as **one** jitted
    ``while_loop`` whose carry holds the current env and the probe word
    (:func:`_guarded_loop_wrap`) — the probe costs one fused reduction per
    ``every`` steps, with a single dispatch per segment and no extra device
    syncs.  A failed probe stops the loop; the host then replays the
    prefix from the retained segment entry to regenerate the last
    probed-good state (the rare path pays the recompute so the happy path
    carries no snapshot) and raises
    :class:`repro.engine.health.NumericalFault` with the step index and the
    last-good state.  That amortization is the ≤2% overhead budget the
    benchmark gates (``benchmarks/health_overhead.py``).
    """
    from repro.engine import health as ehealth

    names = list(env)
    if plan.mesh is None:
        env = {k: fresh_buffer(v) for k, v in env.items()}
    else:
        from jax.sharding import PartitionSpec as P

        _, _, ax_x, ax_y = plan.mesh_ctx
        spec = P(None, ax_x, ax_y, None) if plan.batch > 1 else P(ax_x, ax_y, None)
        sharding = jax.sharding.NamedSharding(plan.mesh, spec)
        env = {k: jax.device_put(fresh_buffer(v), sharding) for k, v in env.items()}

    layout = plan.layout
    use_layout = (
        layout is not None
        and layout.pad > 0
        and any(seg.kind == "fused" for seg in plan.segments)
    )
    events = list(_layout_schedule(plan)) if use_layout else list(plan.segments)
    enter = _guarded_wrap(plan, layout.enter, names) if use_layout else None
    exit_ = _guarded_wrap(plan, layout.exit, names) if use_layout else None

    # probe the entry state too: a poisoned initial condition faults at
    # step 0 with last_good=None rather than masquerading as "last good"
    if not ehealth.probe(env):
        _sentinel_fault(env, 0, None, 0)

    state = {
        "step": 0,  # logical steps completed
        "padded": False,
    }

    def run_loop(step_fn, per_chunk, chunks, steps_per_chunk):
        """One guarded while_loop over `chunks` chunks of `per_chunk`
        launches; env at entry has passed the previous probe, so replaying
        a prefix from it always lands on a probed-good state."""
        nonlocal env
        if chunks <= 0:
            return
        pad = layout.pad if state["padded"] else 0
        runner = _guarded_loop_wrap(plan, step_fn, per_chunk, names, pad)
        entry = env  # retained: the failure path replays the good prefix
        new_env, i, ok = runner(entry, chunks)
        i = int(jax.device_get(i))
        stats.health_probes += i
        done = state["step"] + i * steps_per_chunk
        if not bool(jax.device_get(ok)):
            # the loop stops on the first failed probe, so i >= 1 and the
            # first i-1 chunks all probed finite — rerun just those to
            # recover the last-good state (deterministic compiled body)
            good = runner(entry, i - 1)[0] if i > 1 else entry
            good_step = state["step"] + (i - 1) * steps_per_chunk
            exit_fn = exit_ if state["padded"] else None
            _sentinel_fault(new_env, done, good, good_step, exit_fn)
        env = new_env
        state["step"] = done

    def chunked(step_fn, launches, steps_per_launch):
        """Split `launches` calls of step_fn into probe-granule chunks."""
        if launches <= 0:
            return
        per_chunk = max(1, -(-every // steps_per_launch))  # ceil
        per_chunk = min(per_chunk, launches)
        full, tail = divmod(launches, per_chunk)
        run_loop(step_fn, per_chunk, full, per_chunk * steps_per_launch)
        if tail:
            run_loop(step_fn, tail, 1, tail * steps_per_launch)

    for ev in events:
        if ev == "enter":
            env = enter(env)
            state["padded"] = True
            continue
        if ev == "exit":
            env = exit_(env)
            state["padded"] = False
            continue
        seg = ev
        if seg.loop is None:
            run_loop(seg.step, 1, 1, 1)
            continue
        n, k = seg.loop.n, seg.time_tile
        if k > 1:
            chunked(seg.step, n // k, k)
            chunked(seg.step_rem, n % k, 1)
        else:
            chunked(seg.step, n, 1)
    if state["padded"]:
        env = exit_(env)
    _account(plan)
    return {k: np.asarray(jax.device_get(v)) for k, v in env.items()}


def execute(plan: ExecutionPlan, env: Dict[str, np.ndarray], options=None):
    """Run the plan from ``env`` (name -> (X, Y, Z) array); returns the final
    env as host NumPy arrays.  Updates :data:`repro.engine.stats`; the
    call is one ``wfa.engine.execute`` span.

    Fires the engine's step hook (:mod:`repro.engine.hooks`) before any
    state advances, so an installed fault injector interrupts the run where
    a dead device would — before this execution, after the previous one.

    ``options=RunOptions(check_finite=N)`` routes through the guarded
    chunked runners (:func:`_run_guarded`): an ``isfinite`` sentinel every
    ~N steps, aborting with :class:`repro.engine.health.NumericalFault`
    instead of returning poisoned state.  ``check_finite=0`` (default) is
    the sentinel-free fast path — bitwise identical to previous behavior.
    """
    check = int(getattr(options, "check_finite", 0) or 0)
    fire_step_hook(stats.steps_run, tag="execute")
    with span("wfa.engine.execute"):
        if plan.backend == "numpy":
            out = _run_numpy(plan, env, check)
        elif check > 0:
            out = _run_guarded(plan, env, check)
        elif plan.mesh is None:
            out = _run_single(plan, env)
        else:
            out = _run_sharded(plan, env)
        return {k: np.asarray(v) for k, v in out.items()}


def run_program(
    program,
    env: Dict[str, np.ndarray] = None,
    options=None,
    *,
    backend=None,
    mesh=None,
    time_tile=None,
    resident=None,
):
    """plan + execute in one call (the ``WFAInterface.make`` entry point).

    Policy travels as ``options=RunOptions(...)`` (a bare string is the
    backend); the legacy keywords forward into the bundle without a
    deprecation warning — this is an internal entry point, and the public
    shims (``make``/``run_sharded``/``engine.plan``) already warned.
    ``options.batch=B`` expects every env buffer stacked to ``(B, X, Y, Z)``.
    ``resident=False`` forces the legacy repack-per-launch stepping (the
    bitwise reference for the halo-resident layout).

    With ``options.recovery.detile_explicit`` (and sentinels armed via
    ``check_finite``), a :class:`~repro.engine.health.NumericalFault` from
    an aggressively scheduled plan (time-tiled or overlap-split) triggers
    one de-escalated retry — ``time_tile=1``, ``overlap=False`` — before
    the fault propagates: the conservative schedule changes rounding, the
    cheapest recovery for a marginal explicit run."""
    from repro.engine.options import RunOptions
    from repro.engine.plan import plan as _plan

    if options is None:
        options = RunOptions()
    elif isinstance(options, str):
        options = RunOptions(backend=options)
    overrides = {
        k: v
        for k, v in (
            ("backend", backend),
            ("mesh", mesh),
            ("time_tile", time_tile),
            ("resident", resident),
        )
        if v is not None
    }
    if overrides:
        options = options.replace(**overrides)
    p = _plan(program, options)
    if env is None:
        env = {n: f.init_data for n, f in program.fields.items()}
    if p.batch > 1:
        # a batched plan steps (B, X, Y, Z) stacks; broadcast any field the
        # caller supplied unstacked (identical members — Ensemble overrides
        # arrive already stacked)
        env = {
            k: (
                np.broadcast_to(v, (p.batch,) + np.shape(v)).copy()
                if np.ndim(v) == 3
                else v
            )
            for k, v in env.items()
        }
    try:
        return execute(p, env, options)
    except Exception as fault:
        from repro.engine import health as ehealth

        if not isinstance(fault, ehealth.NumericalFault):
            raise
        rec = options.recovery
        aggressive = any(
            seg.time_tile > 1 or seg.split for seg in p.segments
        )
        if rec is None or not rec.detile_explicit or not aggressive:
            raise
        import logging

        logging.getLogger("repro.engine").warning(
            "explicit sentinel tripped at step %s; retrying with the "
            "conservative schedule (time_tile=1, overlap off)",
            fault.step,
        )
        stats.recovery_attempts += 1
        opts2 = options.replace(time_tile=1, overlap=False)
        return execute(_plan(program, opts2), env, opts2)


# ---------------------------------------------------------------------------
# reverse-mode AD: checkpointed differentiable stepping
# ---------------------------------------------------------------------------


def _diff_launch(step, ref_step):
    """Wrap one compiled launch in a ``custom_vjp``.

    The primal runs the fused kernel; the backward pass differentiates the
    *roll-interpreter* application of the same body at the saved input env —
    for the (bi)linear bodies the compiler fuses, that VJP is exactly the
    transpose of the kernel's map (both compute the same function; the
    bitwise backend-agreement tests pin it), so the gradient is exact while
    the forward sweep stays on the compiled path."""

    @jax.custom_vjp
    def f(env):
        return step(env)

    def fwd(env):
        return step(env), env

    def bwd(env, ct):
        _, pullback = jax.vjp(ref_step, env)
        return pullback(ct)

    f.defvjp(fwd, bwd)
    return f


def _chunked(launch, env, n: int, chunk: int, checkpoint: bool):
    """Run ``n`` launches, rematerializing in chunks of ``chunk``.

    ``jax.checkpoint`` over each chunk runner caps the reverse pass's saved
    residuals at O(n/chunk + chunk) envs instead of O(n) — the classic
    two-level ladder.  ``checkpoint=False`` is the all-residuals reference
    the ~1 ulp property test compares against."""
    if n <= 0:
        return env

    def chunk_fn(e, size):
        for _ in range(size):
            e = launch(e)
        return e

    if not checkpoint or n <= chunk:
        return chunk_fn(env, n)
    full, tail = divmod(n, chunk)
    ck = jax.checkpoint(lambda e: chunk_fn(e, chunk))
    env, _ = jax.lax.scan(lambda e, _: (ck(e), None), env, None, length=full)
    return chunk_fn(env, tail)


def differentiable_runner(
    plan: ExecutionPlan, *, checkpoint: bool = True, chunk_steps: int = None
):
    """Reverse-differentiable ``run(env) -> env`` for a differentiable plan.

    Requires a plan built with ``RunOptions(differentiable=True)`` (repack
    steps, no donation, no in-place residency).  Fused segments keep their
    compiled kernels on the primal sweep — each launch is wrapped in a
    ``custom_vjp`` whose backward differentiates the equivalent interpreter
    application (see :func:`_diff_launch`) — and the time loop is a
    checkpointed ladder: chunk runners of ``chunk_steps`` steps (snapped to
    the segment's time-tile factor ``k``, default ``k·ceil(sqrt(launches))``)
    rematerialize under ``jax.checkpoint``, so reverse-pass memory scales
    with the square root of the step count rather than linearly.

    ``checkpoint=False`` keeps every launch's residuals — the reference the
    checkpointed gradients are tested against.  On a mesh plan the returned
    runner maps the same ladder over bricks inside ``shard_map`` (ppermute
    carries its own transpose rule, so the exchange reverses exactly).

    The result is a plain traceable function: compose with ``jax.jit`` /
    ``jax.grad`` at the call site.  For step counts whose residuals exceed
    device memory even checkpointed, see :func:`checkpointed_vjp` (host /
    disk spill).
    """
    if not plan.differentiable:
        raise ValueError(
            "differentiable_runner needs a plan built with "
            "RunOptions(differentiable=True)"
        )
    if plan.backend == "numpy":
        raise ValueError("the eager numpy backend is not differentiable")
    from repro.engine.plan import compile_body

    shapes = {n: f.shape for n, f in plan.program.fields.items()}
    dtypes = {n: f.dtype for n, f in plan.program.fields.items()}

    staged = []
    for seg in plan.segments:
        if seg.kind == "fused":
            ref1, _ = compile_body(
                seg.ops,
                seg.loop,
                shapes,
                dtypes,
                "jit",
                mesh_ctx=plan.mesh_ctx,
                batch=plan.batch,
            )

            def _ref_k(e, _ref=ref1, _k=seg.time_tile):
                for _ in range(_k):
                    e = _ref(e)
                return e

            launch = _diff_launch(seg.step, _ref_k)
            launch_rem = (
                _diff_launch(seg.step_rem, ref1)
                if seg.step_rem is not None
                else None
            )
        else:
            launch, launch_rem = seg.step, seg.step
        staged.append((seg, launch, launch_rem))

    def run(env):
        env = dict(env)
        for seg, launch, launch_rem in staged:
            if seg.loop is None:
                env = launch(env)
                continue
            n, k = seg.loop.n, seg.time_tile
            if k > 1:
                chunk = max(1, (chunk_steps or 0) // k) or None
                launches = n // k
                chunk = chunk or max(1, int(np.ceil(np.sqrt(max(1, launches)))))
                env = _chunked(launch, env, launches, chunk, checkpoint)
                env = _chunked(launch_rem, env, n % k, max(1, n % k), checkpoint)
            else:
                chunk = chunk_steps or max(1, int(np.ceil(np.sqrt(max(1, n)))))
                env = _chunked(launch, env, n, chunk, checkpoint)
        return env

    if plan.mesh is None:
        return run

    from jax.sharding import PartitionSpec as P

    from repro.core.jaxcompat import shard_map

    _, _, ax_x, ax_y = plan.mesh_ctx
    spec = P(None, ax_x, ax_y, None) if plan.batch > 1 else P(ax_x, ax_y, None)
    specs = {k: spec for k in plan.program.fields}
    return shard_map(
        run, mesh=plan.mesh, in_specs=(specs,), out_specs=specs, check=False
    )


def checkpointed_vjp(chunk_fn, env0, n_chunks: int, *, spill_dir: str = None):
    """Out-of-core reverse sweep: spill chunk-boundary states, replay back.

    For runs whose checkpointed residual ladder still exceeds device memory,
    this trades the in-device ``jax.checkpoint`` ladder for host-side chunk
    snapshots: the forward sweep applies ``chunk_fn`` (any differentiable
    ``env -> env``, e.g. one chunk of :func:`differentiable_runner` steps)
    ``n_chunks`` times, saving each chunk's *input* env — to host memory, or
    to disk via :class:`repro.checkpoint.manager.CheckpointManager` when
    ``spill_dir`` is given (atomic npz snapshots, restored with their exact
    dtypes).  Returns ``(env_final, vjp_fn)``; ``vjp_fn(cotangent_env)``
    replays the chunks newest-first, restoring each saved state and pulling
    the cotangent back through ``jax.vjp(chunk_fn, state)`` — peak device
    memory is one chunk's residuals regardless of run length.
    """
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1; got {n_chunks}")
    manager = None
    snaps = []
    if spill_dir is not None:
        from repro.checkpoint.manager import CheckpointManager

        manager = CheckpointManager(spill_dir, keep=n_chunks)
    env = {k: jnp.asarray(v) for k, v in env0.items()}
    for i in range(n_chunks):
        if manager is not None:
            manager.save(i, env)
        else:
            snaps.append(env)
        env = chunk_fn(env)
    final = env

    def vjp_fn(ct):
        ct = {k: jnp.asarray(v) for k, v in ct.items()}
        for i in reversed(range(n_chunks)):
            if manager is not None:
                saved, _, _ = manager.restore(final, step=i)
            else:
                saved = snaps[i]
            _, pullback = jax.vjp(chunk_fn, saved)
            (ct,) = pullback(ct)
        return ct

    return final, vjp_fn
