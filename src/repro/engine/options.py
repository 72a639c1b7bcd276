"""``RunOptions`` — one frozen bundle for every execution-policy knob.

The execution entry points (``WFAInterface.make``, ``run_sharded``,
``wfa.solve``, ``engine.plan``) each grew the same ad-hoc ``backend=`` /
``mesh=`` / ``time_tile=`` / ``resident=`` keyword sprawl; this module
replaces all of it with a single frozen :class:`RunOptions` value accepted
by all four — now also carrying ``batch=``, the leading ensemble axis that
one kernel launch advances (see :mod:`repro.core.ensemble`).

The legacy keywords still work everywhere as thin deprecation shims: they
warn **once per entry point per keyword** and forward into the options
bundle (an explicit legacy keyword overrides the same field of a passed
``options=``, so half-migrated call sites behave predictably).

>>> opts = RunOptions(backend="pallas", time_tile=4, batch=8)
>>> opts.batch, opts.resident
(8, True)
>>> opts.replace(batch=1).batch
1
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Set, Tuple


class _Unset:
    """Sentinel distinguishing "not passed" from an explicit ``None``."""

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return "<unset>"


UNSET = _Unset()

#: (entry point, keyword) pairs that already warned this process
_WARNED: Set[Tuple[str, str]] = set()


@dataclasses.dataclass(frozen=True)
class RunOptions:
    """Execution policy for one plan/run, shared by every entry point.

    ``backend=None`` means "the entry point's default" (``make`` defaults to
    ``jit``, ``wfa.solve`` to ``pallas``, ``run_sharded`` to ``jit``), so
    one options value can travel between entry points without pinning a
    backend.  ``batch`` is the leading ensemble axis: every field buffer
    grows a ``(B, ...)`` leading dimension and one kernel launch advances
    all ``B`` members (``batch=1`` is the classic single-scenario path).

    ``overlap`` selects the interior/boundary kernel split that hides the
    halo exchange behind interior compute (resident pallas plans only):
    ``True`` forces the split wherever it is legal, ``False`` keeps the
    monolithic fused launch, and ``"auto"`` (the default) splits only when
    the measured cost model (:mod:`repro.core.perfmodel`) holds a
    calibrated entry for the body predicting the split faster — so
    uncalibrated runs keep today's schedule.

    ``differentiable=True`` builds the run for reverse-mode AD: jitted
    runners stop donating their entry buffers (donated buffers cannot be
    saved as VJP residuals, and callers keep their arrays), plans skip the
    halo-resident layout, and ``wfa.solve`` routes through the
    implicit-function-theorem adjoint (:mod:`repro.solver.adjoint`).

    ``recovery=RecoveryPolicy(...)`` (:mod:`repro.solver.health`) arms the
    implicit path's escalation ladder — a failed solve restarts/escalates/
    re-runs at fp64 per the policy and raises ``NumericalFault`` when
    exhausted — and lets explicit plans de-escalate (``time_tile=1``,
    ``overlap=False``) after a sentinel trip.  ``check_finite=N > 0`` arms
    the explicit path's ``isfinite`` sentinel every N steps (amortized at
    the chunk granule; 0 — the default — keeps benchmarks probe-free).
    """

    backend: Optional[str] = None
    mesh: Optional[object] = None
    time_tile: Optional[int] = None
    resident: bool = True
    batch: int = 1
    overlap: object = "auto"
    differentiable: bool = False
    recovery: Optional[object] = None
    check_finite: int = 0

    def __post_init__(self):
        if int(self.batch) < 1:
            raise ValueError(f"batch must be >= 1; got {self.batch}")
        object.__setattr__(self, "batch", int(self.batch))
        if self.overlap not in (True, False, "auto"):
            raise ValueError(
                f"overlap must be True, False or 'auto'; got {self.overlap!r}"
            )
        if self.differentiable not in (True, False):
            raise ValueError(
                f"differentiable must be a bool; got {self.differentiable!r}"
            )
        if int(self.check_finite) < 0:
            raise ValueError(
                f"check_finite must be >= 0 (0 disables); got {self.check_finite}"
            )
        object.__setattr__(self, "check_finite", int(self.check_finite))
        if self.recovery is not None:
            from repro.solver.health import RecoveryPolicy

            if not isinstance(self.recovery, RecoveryPolicy):
                raise TypeError(
                    "recovery must be a repro.solver.health.RecoveryPolicy; "
                    f"got {type(self.recovery).__name__}"
                )

    def replace(self, **changes) -> "RunOptions":
        """A copy with ``changes`` applied (``dataclasses.replace``)."""
        return dataclasses.replace(self, **changes)

    def resolved_backend(self, default: str) -> str:
        return default if self.backend is None else self.backend


def _warn_once(entry: str, kwarg: str, hint: str) -> None:
    key = (entry, kwarg)
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(
        f"{entry}({kwarg}=...) is deprecated; pass "
        f"options=wfa.RunOptions({hint}) instead",
        DeprecationWarning,
        stacklevel=4,
    )


def resolve_options(options, entry: str, **legacy) -> RunOptions:
    """Fold an ``options=`` value and legacy keywords into one RunOptions.

    ``legacy`` maps RunOptions field names to the entry point's keyword
    values, with :data:`UNSET` marking "not passed".  Every explicitly
    passed legacy keyword emits one :class:`DeprecationWarning` per entry
    point and overrides the corresponding field of ``options``.  A bare
    string ``options`` is accepted as the backend (the historical
    positional-``backend`` spelling of ``plan``).
    """
    if options is None:
        options = RunOptions()
    elif isinstance(options, str):
        options = RunOptions(backend=options)
    elif not isinstance(options, RunOptions):
        raise TypeError(
            f"options must be a RunOptions (or backend string); "
            f"got {type(options).__name__}"
        )
    given = {k: v for k, v in legacy.items() if not isinstance(v, _Unset)}
    for k, v in given.items():
        _warn_once(entry, k, f"{k}={v!r}")
    if given:
        options = dataclasses.replace(options, **given)
    return options
