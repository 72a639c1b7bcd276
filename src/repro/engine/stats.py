"""Execution counters, host spans and device scopes for the unified engine.

One global :data:`stats` instance (mirroring ``repro.compiler.stats``) that
:func:`repro.engine.plan`, the runners of :func:`repro.engine.single_runner`
and :func:`repro.engine.sharded_runner` (once per dispatch) and
:func:`repro.engine.execute` update in place;
tests and benchmarks ``reset_stats()`` around a run and assert on the
communication accounting — the headline being :attr:`EngineStats.
exchanges_per_step`, which temporal blocking must drop k×.

Spans and scopes name the program's own work, ``wfa.<layer>.<what>``:

* :func:`span` brackets host code (dispatch, the solver's entry copy,
  ``execute``): a ``jax.profiler.TraceAnnotation`` for a profiler trace,
  and a record in a bounded in-memory ring on the host clock
  (``time.perf_counter_ns``), read with :func:`spans`;
* ``jax.named_scope`` names device work inside the jitted programs
  (``wfa.engine.wrap_pad``, ``.margin_refresh``, ``.layout``,
  ``wfa.kernel.stencil``, ``wfa.krylov.dot``, ``.update``, and on a mesh
  ``wfa.halo.exchange`` — the ``ppermute`` transfers and the slabs they
  send — and ``wfa.halo.pad``, the whole-brick pad that repacks a brick
  for its halo); the runners
  :func:`record_program` what they compile, and :func:`device_scopes`
  maps each compiled instruction to its innermost scope, so a device
  trace's operations can be split by the code that issued them.

Exchange counting is *static*: execution is traced (``lax.fori_loop`` /
``shard_map``), so the executor derives the counts from the plan — one pad /
halo-exchange event per fused-kernel launch (zero for halo-free bodies, the
wrap pad on a single device counts as the exchange analogue), and one event
per op application on the roll-interpreter paths (which pad per op, per
step).
"""

from __future__ import annotations

import collections
import dataclasses
import re
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class EngineStats:
    """Counters for engine planning + execution (reset with ``reset_stats``).

    One live instance is exported as ``repro.engine.stats``; read the
    counters after a run (and ``reset_stats()`` between runs you want to
    compare):

    >>> from repro.engine import reset_stats, stats
    >>> reset_stats()
    >>> (stats.steps_run, stats.exchanges_per_step, stats.mg_levels_built)
    (0, 0.0, 0)
    """

    plans_built: int = 0
    bodies_compiled: int = 0  # compile_body calls (every backend dispatch)
    segments_fused: int = 0  # loop bodies routed to a fused kernel
    segments_interp: int = 0  # loop bodies routed to the roll interpreter
    steps_run: int = 0  # logical time steps executed
    launches: int = 0  # kernel / interpreter-step invocations
    exchanges: int = 0  # halo exchanges, wrap pads or margin refreshes
    #: bytes one chip sends by ppermute (the mean over a mesh's chips),
    #: from the slab shapes of each traced mesh step times its launches
    halo_bytes: float = 0.0
    tiles_fused: int = 0  # k>1 tiled launches (k steps per launch)
    resident_runs: int = 0  # executions stepping on a halo-resident layout
    #: full-field pad/copy conversions: one per fused launch on the legacy
    #: path; on a resident run only the layout enter/exit events (2 for an
    #: all-fused plan, +2 around each interpreter segment in a mixed plan)
    repacks: int = 0
    max_time_tile: int = 1  # largest k any segment ran with
    tile_reasons: Tuple[str, ...] = ()  # why a tile factor was clamped/refused

    # -- exchange/compute overlap (interior/boundary split segments) ---------
    interior_launches: int = 0  # interior-region kernel launches
    boundary_launches: int = 0  # boundary shell kernel launches
    #: halo exchanges whose slabs travelled concurrently with an interior
    #: launch (one per split-segment tile; the overlap the split exists for)
    overlapped_exchanges: int = 0
    cost_model_hits: int = 0  # plans served by a calibrated cost-model entry
    calibrations: int = 0  # cost-model calibration runs performed
    mg_hierarchies: int = 0  # multigrid hierarchies scheduled
    mg_levels_built: int = 0  # level segments compiled across hierarchies
    #: (shape, smoother-fused, residual-fused) per level of the last hierarchy
    mg_level_log: Tuple[Tuple[Tuple[int, int, int], bool, bool], ...] = ()
    #: level pairs whose restriction/prolongation run as the jnp references
    #: because Mosaic cannot compile the transfer kernels
    mg_transfer_refs: int = 0

    # -- batched ensembles (plans with options.batch > 1) -------------------
    ensemble_runs: int = 0  # executes of a batched plan (one launch, B members)
    ensemble_members: int = 0  # summed B over those executes
    #: per-member Krylov iteration counts of the last batched solve — the
    #: masked loop runs to the slowest member, but each member's own count
    #: freezes when its residual converges (see repro.solver.krylov)
    member_iterations: Tuple[int, ...] = ()

    # -- numerical health (guarded iterations + explicit sentinels) ----------
    health_probes: int = 0  # explicit-path isfinite sentinel evaluations
    numerical_faults: int = 0  # NumericalFaults raised (solver or sentinel)
    recovery_attempts: int = 0  # escalation-ladder re-solves driven
    #: distinct solver outcome words of the last wfa.solve call
    solve_outcomes: Tuple[str, ...] = ()

    # -- serving tier (updated by repro.service under its stats lock) -------
    requests_admitted: int = 0  # requests accepted into the bounded queue
    requests_rejected: int = 0  # admission-control rejections (queue full)
    requests_expired: int = 0  # dropped at dispatch: deadline already passed
    requests_completed: int = 0  # requests that returned a result
    requests_failed: int = 0  # requests that exhausted their retries
    requests_degraded: int = 0  # served via the interpreter fallback path
    request_retries: int = 0  # restore-and-continue attempts across requests
    plan_builds: int = 0  # service plan-cache misses (compile paid)
    plan_cache_hits: int = 0  # requests served from a warm plan
    service_checkpoints: int = 0  # resident-state snapshots written
    service_restores: int = 0  # checkpoints restored (mid-flight resume)
    service_stragglers: int = 0  # HeartbeatMonitor flags across workers
    queue_wait_s: float = 0.0  # summed submit -> dispatch wait

    @property
    def exchanges_per_step(self) -> float:
        """Halo exchanges (or wrap pads) per logical time step."""
        return self.exchanges / self.steps_run if self.steps_run else 0.0

    def note_tile_reason(self, reason: str) -> None:
        self.tile_reasons = self.tile_reasons + (reason,)


stats = EngineStats()


def reset_stats() -> None:
    # mutate in place so `from repro.engine import stats` stays live
    for f in dataclasses.fields(EngineStats):
        setattr(stats, f.name, f.default)
    _ring.clear()


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

#: spans kept in memory, newest last; older ones fall off the ring
SPAN_RING = 1 << 16

#: ``(name, start_ns, end_ns, parent)`` per closed span
_ring: collections.deque = collections.deque(maxlen=SPAN_RING)
_open = threading.local()


class span:
    """Context manager naming a stretch of host code ``name``.

    It enters a ``jax.profiler.TraceAnnotation(name)``, so a profiler trace
    shows the span on its own clock, and on exit appends ``(name,
    start_ns, end_ns, parent)`` to the in-memory ring that :func:`spans`
    reads: times from ``time.perf_counter_ns()``, ``parent`` the name of
    the span open on the same thread when this one began (``None`` at the
    top).  Always on; no device work and no sync.

    >>> from repro.engine.stats import reset_stats, span, spans
    >>> reset_stats()
    >>> with span("wfa.doc.outer"):
    ...     with span("wfa.doc.inner"):
    ...         pass
    >>> [(name, parent) for name, _, _, parent in spans()]
    [('wfa.doc.inner', 'wfa.doc.outer'), ('wfa.doc.outer', None)]
    """

    __slots__ = ("name", "_parent", "_note", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = _open.__dict__.setdefault("stack", [])
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        self._note = TraceAnnotation(self.name)
        self._note.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._note.__exit__(*exc)
        _open.stack.pop()
        _ring.append((self.name, self._t0, t1, self._parent))
        return False


def spans() -> List[Tuple[str, int, int, Optional[str]]]:
    """The closed spans in the ring, oldest first."""
    return list(_ring)


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------

#: jitted function -> its argument shapes, for every program a runner built
#: (weakly held: a runner's program is forgotten with the runner)
_programs: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s+=\s+(.*?)\s[a-z][a-z0-9\-]*\(")
_SHAPE = re.compile(r"\b(pred|bf16|[fsuc][0-9]+)\[([0-9,]*)\]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"wfa\.[a-z0-9_]+\.[a-z0-9_]+")


def record_program(fn, args) -> None:
    """Remember jitted ``fn`` and the arguments (arrays or
    ``jax.ShapeDtypeStruct``s) it runs on, for :func:`device_scopes`."""
    _programs[fn] = args


def instruction_key(text: str):
    """``(name, result shapes)`` of one instruction line of compiled HLO
    text, as a device trace prints it: ``("fusion.6",
    ("f32[516,516,128]",))``; ``None`` for a line that is not one."""
    m = _INSTRUCTION.match(text)
    if m is None:
        return None
    return m.group(1), tuple(f"{dt}[{dims}]" for dt, dims in _SHAPE.findall(m.group(2)))


def device_scopes() -> Dict[tuple, str]:
    """Map each instruction of every recorded program, keyed by
    :func:`instruction_key`, to the innermost ``wfa.*`` scope of its
    ``op_name``; instructions outside every scope are left out.

    Lowers and compiles each program again (a hit in JAX's compile caches
    where the program ran) and reads its compiled text.  Instruction names
    are unique only within one program, so a key that two recorded
    programs give different scopes, or one a scope and the other none, is
    left out too: an operation is never put under a scope it may not
    belong to.  Programs that were not recorded are not checked."""
    seen: Dict[tuple, Optional[str]] = {}
    clash = set()
    for fn, args in list(_programs.items()):
        text = fn.lower(*args).compile().as_text()
        for line in text.splitlines():
            key = instruction_key(line)
            if key is None:
                continue
            name = _OP_NAME.search(line)
            found = _SCOPE.findall(name.group(1)) if name else []
            scope = found[-1] if found else None
            if seen.setdefault(key, scope) != scope:
                clash.add(key)
    return {k: s for k, s in seen.items() if s is not None and k not in clash}


def service_stats() -> dict:
    """Service-level summary the benchmark and CI smoke gate on.

    Combines the serving-tier counters above with the kernel-pipeline
    counters of :data:`repro.compiler.stats` (the fallback count is the
    "unexpected interpreter fallbacks" gate on a no-fault run).

    >>> from repro.engine import reset_stats
    >>> from repro.engine.stats import service_stats
    >>> reset_stats()
    >>> s = service_stats()
    >>> (s["requests"]["completed"], s["plans"]["cache_hits"], s["faults"]["retries"])
    (0, 0, 0)
    """
    from repro.compiler import stats as kstats

    admitted = stats.requests_admitted
    return {
        "requests": {
            "admitted": admitted,
            "rejected": stats.requests_rejected,
            "expired": stats.requests_expired,
            "completed": stats.requests_completed,
            "failed": stats.requests_failed,
            "degraded": stats.requests_degraded,
            "mean_queue_wait_s": (
                stats.queue_wait_s / admitted if admitted else 0.0
            ),
        },
        "plans": {
            "builds": stats.plan_builds,
            "cache_hits": stats.plan_cache_hits,
        },
        "kernels": {
            "built": kstats.kernels_built,
            "cache_hits": kstats.cache_hits,
            "fallbacks": kstats.fallbacks,
            "launches": stats.launches,
        },
        "faults": {
            "retries": stats.request_retries,
            "checkpoints": stats.service_checkpoints,
            "restores": stats.service_restores,
            "stragglers": stats.service_stragglers,
        },
        "health": {
            "probes": stats.health_probes,
            "numerical_faults": stats.numerical_faults,
            "recovery_attempts": stats.recovery_attempts,
        },
        "steps_run": stats.steps_run,
        "repacks": stats.repacks,
    }
