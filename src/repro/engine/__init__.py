"""repro.engine — the unified execution engine (planner + executor).

Every way of running a recorded WFA program — ``WFAInterface.make`` (all
backends), ``core.halo.run_sharded`` and the operator/rhs applications
behind ``wfa.solve`` — dispatches through this package:

* :func:`plan` schedules the program's op groups into
  :class:`~repro.engine.plan.Segment`s (fused kernel vs interpreter, with a
  time-tile factor per loop body);
* :func:`execute` runs a plan eagerly (``numpy``), under one ``jax.jit``
  (single device) or inside one ``shard_map`` (mesh);
* :func:`compile_body` builds a single body application ``env -> env`` —
  the one backend if/else in the tree — for the solver's matrix-free
  operator steps;
* :data:`stats` exposes the communication accounting (steps, launches,
  halo exchanges / wrap pads, tiles fused); :func:`span`, :func:`spans`
  and :func:`device_scopes` name the program's host and device work.

Temporal blocking: a fused segment with ``time_tile=k`` advances k steps
per kernel launch off one halo exchange (or wrap pad) of depth ``k·h`` —
the wafer-scale trapezoid schedule (Rocki et al.) on the TPU mesh.  Pass
``time_tile=`` through ``make``/``run_sharded`` to override the planner's
auto-pick; illegal factors clamp with a logged reason, non-lowerable bodies
fall back to the untiled interpreter exactly as before.
"""

from repro.engine import health
from repro.engine.executor import (
    checkpointed_vjp,
    differentiable_runner,
    execute,
    run_program,
    sharded_runner,
    single_runner,
)
from repro.engine.health import NumericalFault, RecoveryPolicy
from repro.engine.layout import HaloLayout
from repro.engine.options import UNSET, RunOptions, resolve_options
from repro.engine.plan import (
    BACKENDS,
    ExecutionPlan,
    LevelSegment,
    Segment,
    compile_body,
    plan,
    plan_mg_levels,
)
from repro.engine.stats import (
    EngineStats,
    device_scopes,
    reset_stats,
    service_stats,
    span,
    spans,
    stats,
)

__all__ = [
    "BACKENDS",
    "EngineStats",
    "ExecutionPlan",
    "HaloLayout",
    "LevelSegment",
    "NumericalFault",
    "RecoveryPolicy",
    "RunOptions",
    "Segment",
    "UNSET",
    "compile_body",
    "device_scopes",
    "execute",
    "health",
    "plan",
    "plan_mg_levels",
    "reset_stats",
    "resolve_options",
    "checkpointed_vjp",
    "differentiable_runner",
    "run_program",
    "service_stats",
    "sharded_runner",
    "single_runner",
    "span",
    "spans",
    "stats",
]
