"""The one traffic generator: what every closed loop shares.

A traffic file names its ``loop``, found by name in ``bench/loops/``, and
the loop's parameters; the configuration names its ``scheme``, found by
name in ``bench/schemes/``, which builds what is stepped or solved and
holds its plain reference.  Each loop is a subclass of :class:`Loop` with
three phases:

* ``setup()`` builds the system, makes the seeded inputs on the device and
  runs every shape the window will use once, so nothing compiles later;
* ``window(seconds)`` drives the closed loop for ``seconds`` and returns a
  :class:`Window` of completed units (chunks or solves), keeping a seeded
  sample of the answers;
* ``check()``, after the window and after the program's state is freed,
  compares the sampled answers with the scheme's plain reference and
  returns each compared number by the name its limit has in the
  configuration's ``check``.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

#: the precision a control computes in, one below the configuration's
LOWER = {"float32": "bfloat16", "float64": "float32"}


@dataclasses.dataclass
class Stepper:
    """``run(x) -> x`` advances ``steps`` steps; its argument is consumed."""

    run: Callable
    steps: int
    info: dict


@dataclasses.dataclass
class Solver:
    """``solve(b) -> (x, raw)`` dispatches one solve: the answer and the
    solver's own report, both on the device.  ``read(raw)``, given the
    report on the host, gives ``(iterations, converged, residual)``: the
    iterations taken, whether the solver says it met its tolerance, and the
    residual norm it stopped at."""

    solve: Callable
    read: Callable
    info: dict


@dataclasses.dataclass
class Unit:
    """One chunk or solve: host-clock start and end, whether it succeeded,
    and the cell updates it did."""

    start: float
    end: float
    ok: bool
    work: float = 0.0
    extra: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Window:
    start: float
    end: float
    units: List[Unit]
    compiles: int = 0

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def done(self) -> List[Unit]:
        return [u for u in self.units if u.ok]

    @property
    def attempted(self) -> int:
        return len(self.units)

    @property
    def failed(self) -> int:
        return sum(not u.ok for u in self.units)


class Compiles:
    """Counts executables built or loaded (``backend_compile`` events)."""

    n = 0
    _installed = False

    @classmethod
    def install(cls) -> None:
        if cls._installed:
            return
        from jax._src import dispatch

        def listen(event, duration, **kw):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                cls.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)
        cls._installed = True


class GcPauses:
    """Python's garbage collections, each ``(start, end, generation)`` on
    the host clock and a ``bench.gc`` span in a trace, so a host stall can
    be told from a collection."""

    events: list = []
    _installed = False
    _open: list = []

    @classmethod
    def install(cls) -> None:
        if cls._installed:
            return

        def callback(phase, info):
            if phase == "start":
                span = jax.profiler.TraceAnnotation("bench.gc")
                span.__enter__()
                cls._open.append((time.perf_counter(), span))
            elif cls._open:
                t0, span = cls._open.pop()
                span.__exit__(None, None, None)
                cls.events.append((t0, time.perf_counter(), info["generation"]))

        gc.callbacks.append(callback)
        cls._installed = True

    @classmethod
    def between(cls, lo: float, hi: float) -> list:
        return [e for e in cls.events if e[0] >= lo and e[1] <= hi]


class Reservoir:
    """A seeded uniform sample of ``k`` items from a stream of unknown length
    (algorithm R).  ``wants(i)`` decides, before item ``i`` is produced,
    whether it will be kept, so a loop copies only what it keeps."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng = k, rng
        self.items: dict = {}
        self._slot = None

    def wants(self, i: int) -> bool:
        if i < self.k:
            self._slot = i
        else:
            j = int(self.rng.integers(0, i + 1))
            self._slot = j if j < self.k else None
        return self._slot is not None

    def put(self, item) -> None:
        self.items[self._slot] = item

    def sample(self) -> list:
        return [self.items[s] for s in sorted(self.items)]


def max_rel_err(out, ref) -> float:
    """``max|out − ref| / max|ref|``, in float32."""
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))


def worst(numbers: dict, more: dict) -> dict:
    """``numbers`` with each of ``more`` merged in by its maximum."""
    for k, v in more.items():
        numbers[k] = max(numbers.get(k, v), float(v))
    return numbers


class SchemeError(KeyError):
    """The configuration's scheme lacks what the traffic's loop needs."""


class Loop:
    """The base of every loop in ``bench/loops/``.  ``NEEDS`` names the
    functions the loop calls on the configuration's scheme."""

    NEEDS: tuple = ()

    def __init__(self, config, traffic, seed, scheme, control, devices):
        missing = [n for n in self.NEEDS if not callable(getattr(scheme, n, None))]
        if missing:
            raise SchemeError(f"scheme {config['scheme']!r} has no {missing}, which "
                              f"loop {traffic['loop']!r} needs")
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.scheme, self.control, self.devices = scheme, bool(control), devices
        self.rng = np.random.default_rng(self.seed)
        self.cells = int(np.prod(config["grid"]))
        self.info: dict = {}
