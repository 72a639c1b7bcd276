"""The paper's Fig. 3 explicit heat body (FTCS) spread over a mesh of chips.

Weak scaling (the paper's Fig. 4a / Eq. 6): the global (X, Y, Z) field is
cut into one (X/mx, Y/my, Z) brick a chip over an ``mx`` x ``my`` mesh
(configuration key ``mesh``), and neighbouring bricks exchange their
margins before every launch.  The update is ``ftcs``'s: each step every
cell off the Moat takes ``(1 − 6ω)·T + ω·(sum of its six neighbours)``.
Configuration keys: ``grid`` (global), ``mesh``, ``dtype``, ``omega``,
``plate``.

* :func:`heat_steps` is the plain reference on the global array
  (``jnp.roll``; it imports nothing of the program: the Moat is fixed, so
  a roll's wrap never reaches an updated cell) and :func:`compare_steps`
  the comparison of one answer with it, both gathered on one chip;
* :func:`inputs` makes the seeded hot plates, placed brick by brick;
* :func:`stepper` is what the ``stepping`` loop drives: the recorded body
  planned by ``repro.engine`` on the mesh and run by its ``shard_map``
  runner, or, for the control, the reference in its place one precision
  down.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import fields
from bench.harness.generator import LOWER, Stepper, max_rel_err
from bench.schemes.ftcs import fig3_program

#: the mesh's axis names: X over the first, Y over the second
AXES = ("x", "y")

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
    """``max_rel_err`` of ``out`` against ``steps`` reference steps from
    ``inp``, both gathered on the first chip."""
    one = jax.devices()[0]
    inp, out = jax.device_put(inp, one), jax.device_put(out, one)
    ref = heat_steps(inp, float(config["omega"]), int(steps), config["dtype"])
    return {"max_rel_err": max_rel_err(out, ref)}


# ---------------------------------------------------------------------------
# the system under test and its control
# ---------------------------------------------------------------------------


def sharding(config: dict):
    """Bricks over the first ``mx·my`` devices: X over the mesh's first
    axis, Y over its second, Z whole on every chip."""
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    mx, my = config["mesh"]
    devices = np.array(jax.devices()[: mx * my]).reshape(mx, my)
    mesh = Mesh(devices, AXES, axis_types=(AxisType.Auto,) * 2)
    return NamedSharding(mesh, PartitionSpec(*AXES, None))


def inputs(config: dict, seed: int, count: int):
    """The seeded hot plates of ``ftcs`` at the global grid, made brick by
    brick on the chips that hold them."""
    make = jax.jit(lambda: fields.plates(seed, config["grid"], count,
                                         config["plate"], config["dtype"]),
                   out_shardings=(sharding(config),) * count)
    return make()


def stepper(config: dict, traffic: dict, devices, control: bool) -> Stepper:
    """``traffic["chunk_steps"]`` steps a call on sharded state.  The
    program is the engine's ``shard_map`` runner of the plan built with the
    cell's mesh, with the time tile left to the planner."""
    steps = int(traffic["chunk_steps"])
    c = float(config["omega"])
    if control:
        low = LOWER[config["dtype"]]
        return Stepper(run=lambda T: heat_steps(T, c, steps, low), steps=steps,
                       info={"control": low})
    from repro.engine import plan, sharded_runner
    from repro.engine.options import RunOptions

    sh = sharding(config)
    grid = tuple(config["grid"])
    p = plan(fig3_program(grid, c, steps, config["dtype"]),
             RunOptions(backend="pallas", mesh=sh.mesh))
    runner, _ = sharded_runner(p)
    # tracing the runner notes the bytes each launch sends on its step
    runner.lower({"T": jax.ShapeDtypeStruct(grid, config["dtype"], sharding=sh)})
    seg = p.segments[0]
    mx, my = config["mesh"]
    info = {"time_tile": seg.time_tile, "segment": seg.kind,
            "resident_layout": p.layout.pad > 0, "mesh": [mx, my],
            "brick": [grid[0] // mx, grid[1] // my, grid[2]],
            "halo_bytes_per_launch": getattr(seg.step, "halo_bytes", None)}
    return Stepper(run=lambda T: runner({"T": T})["T"], steps=steps, info=info)
