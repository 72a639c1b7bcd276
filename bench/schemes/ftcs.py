"""The paper's Fig. 3 explicit heat body (FTCS) on one chip.

Each step every cell off the Moat (the x/y faces and the z end planes)
takes ``(1 − 6ω)·T + ω·(sum of its six neighbours)``; the Moat stays
fixed.  Configuration keys: ``grid``, ``dtype``, ``omega``, ``plate``.

* :func:`heat_steps` is the plain reference (``jnp.roll``; it imports
  nothing of the program) and :func:`compare_steps` the comparison of one
  answer with it;
* :func:`inputs` makes the seeded hot plates;
* :func:`stepper` is what the ``stepping`` loop drives: the recorded body
  planned by ``repro.engine`` and run by its compiled single-chip runner,
  or, for the control, the reference in its place one precision down.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import fields
from bench.harness.generator import LOWER, Stepper, max_rel_err

# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("steps", "dtype"))
def heat_steps(T, c: float, steps: int, dtype="float32"):
    """``steps`` FTCS steps computed in ``dtype``, returned in float32."""
    dt = jnp.dtype(dtype)
    T = T.astype(dt)
    mask = fields.interior(T.shape)
    center = jnp.asarray(1.0 - 6.0 * c, dt)
    cc = jnp.asarray(c, dt)

    def step(_, T):
        s = sum(jnp.roll(T, d, a) for a in range(3) for d in (1, -1))
        return jnp.where(mask, center * T + cc * s, T)

    return jax.lax.fori_loop(0, steps, step, T).astype(jnp.float32)


def compare_steps(config: dict, inp, out, steps: int) -> dict:
    """``max_rel_err`` of ``out`` against ``steps`` reference steps from ``inp``."""
    ref = heat_steps(inp, float(config["omega"]), int(steps), config["dtype"])
    return {"max_rel_err": max_rel_err(out, ref)}


def inputs(config: dict, seed: int, count: int):
    return fields.plates(seed, config["grid"], count, config["plate"], config["dtype"])


# ---------------------------------------------------------------------------
# the system under test and its control
# ---------------------------------------------------------------------------


def fig3_program(shape, c: float, steps: int, dtype: str):
    """The Fig. 3 body, recorded through the WFA frontend."""
    from repro.core.field import Field
    from repro.core.program import ForLoop, scoped_program

    center = 1.0 - 6.0 * c
    with scoped_program() as program:
        T = Field("T", shape=tuple(shape), dtype=np.dtype(dtype))
        with ForLoop("time_loop", steps):
            T[1:-1, 0, 0] = center * T[1:-1, 0, 0] + c * (
                T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0] + T[1:-1, 0, -1]
                + T[1:-1, -1, 0] + T[1:-1, 0, 1])
    return program


def stepper(config: dict, traffic: dict, devices, control: bool) -> Stepper:
    """``traffic["chunk_steps"]`` steps a call.  The program is the compiled
    plan runner that ``repro.engine.run_program`` executes, with the time
    tile left to the planner."""
    steps = int(traffic["chunk_steps"])
    c = float(config["omega"])
    if control:
        low = LOWER[config["dtype"]]
        return Stepper(run=lambda T: heat_steps(T, c, steps, low), steps=steps,
                       info={"control": low})
    from repro.engine import plan, single_runner
    from repro.engine.options import RunOptions

    p = plan(fig3_program(config["grid"], c, steps, config["dtype"]),
             RunOptions(backend="pallas"))
    runner = single_runner(p)
    seg = p.segments[0]
    info = {"time_tile": seg.time_tile, "segment": seg.kind,
            "resident_layout": p.layout.pad > 0}
    return Stepper(run=lambda T: runner({"T": T})["T"], steps=steps, info=info)
