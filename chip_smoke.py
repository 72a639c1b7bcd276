#!/usr/bin/env python3
"""Drive the main path once on a TPU at the paper's industrial size.

    python3 chip_smoke.py              # one chip: phases (a)-(d)
    python3 chip_smoke.py --chips 4    # heat3d on a 2x2 mesh vs one chip

One chip, ``HeatConfig()`` (512x512x128 fp32, 3.3e7 cells):

  (a) device check: the platform is ``tpu`` and Pallas kernels compile
      through Mosaic (no interpret mode);
  (b) explicit heat3d: the paper's Fig. 3 body, 64 steps through
      ``wfa.make`` on the pallas backend with the time tile left to the
      planner, against a plain ``jax.numpy`` roll reference on the same
      chip, with zero interpreter fallbacks;
  (c) BTCS solve: ``record_implicit`` + CG on the pallas backend; the
      outcome must be CONVERGED and the true residual, recomputed here with
      plain slicing, small;
  (d) service: a ``SimulationService`` warmed with the heat3d and BTCS
      signatures at the same size serves step and solve requests with no
      retries, no degraded requests and no kernel built after warm-up.

``--chips 4`` runs only heat3d (64 explicit steps and a CG solve) on a 2x2
mesh of four chips, each against the same run on one chip.

Times are informational.  Any failed check raises, so the exit status is
non-zero; the last line of stdout is one JSON object, printed only when
every phase passed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro as wfa  # noqa: E402
from repro.configs.heat3d import HeatConfig, make_field, record_implicit  # noqa: E402

STEPS = 64          # explicit steps per heat3d run
RTOL = 1e-5         # max relative error against a reference run
SOLVE_RTOL = 1e-5   # relative true residual ‖b − A·x‖ / ‖b‖ of the solve


class PhaseFailed(RuntimeError):
    """A smoke check did not hold."""


def require(cond, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def say(*parts) -> None:
    print(*parts, flush=True)


def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


# ---------------------------------------------------------------------------
# programs and plain jnp references
# ---------------------------------------------------------------------------


def fig3_program(cfg: HeatConfig, steps: int = STEPS):
    """The paper's Fig. 3 explicit heat body on ``cfg``'s grid."""
    c = cfg.omega
    center = 1.0 - 6.0 * c
    wse = wfa.WFAInterface()
    T = wfa.Field("T_n", init_data=make_field(cfg))
    with wfa.ForLoop("time_loop", steps):
        T[1:-1, 0, 0] = center * T[1:-1, 0, 0] + c * (
            T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0] + T[1:-1, 0, -1]
            + T[1:-1, -1, 0] + T[1:-1, 0, 1])
    return wse, T


def _interior(shape):
    nx, ny, nz = shape
    ix = jnp.arange(nx)[:, None, None]
    iy = jnp.arange(ny)[None, :, None]
    iz = jnp.arange(nz)[None, None, :]
    return ((ix > 0) & (ix < nx - 1) & (iy > 0) & (iy < ny - 1)
            & (iz > 0) & (iz < nz - 1))


def reference_heat(T0: np.ndarray, c: float, steps: int) -> np.ndarray:
    """``steps`` FTCS steps with ``jnp.roll``: the Moat (x/y faces, z end
    planes) stays fixed, every other cell takes the 7-point update."""
    center = 1.0 - 6.0 * c

    @jax.jit
    def run(T):
        mask = _interior(T.shape)

        def step(_, T):
            s = sum(jnp.roll(T, d, a) for a in range(3) for d in (1, -1))
            return jnp.where(mask, center * T + c * s, T)

        return jax.lax.fori_loop(0, steps, step, T)

    return np.asarray(run(jnp.asarray(T0)))


def btcs_residual(x: np.ndarray, T0: np.ndarray, w: float) -> float:
    """Relative true residual ``‖b − A·x‖ / ‖b‖`` of the paper's BTCS
    system (Eq. 3), applied with plain slicing: ``A = I − ωψ·S`` and
    ``b = ψ·Tⁿ`` on the interior, identity rows on the Moat."""
    psi = 1.0 / (1.0 + 6.0 * w)

    @jax.jit
    def resid(x, T0):
        c = (slice(1, -1),) * 3
        S = (x[2:, 1:-1, 1:-1] + x[:-2, 1:-1, 1:-1] + x[1:-1, 2:, 1:-1]
             + x[1:-1, :-2, 1:-1] + x[1:-1, 1:-1, 2:] + x[1:-1, 1:-1, :-2])
        b = T0.at[c].multiply(psi)
        r = b - x.at[c].add(-w * psi * S)
        return jnp.sqrt(jnp.sum(r * r) / jnp.sum(b * b))

    return float(resid(jnp.asarray(x), jnp.asarray(T0)))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device() -> dict:
    """(a) A TPU is attached and the kernels compile through Mosaic."""
    from repro.kernels.ops import _interpret

    dev = jax.devices()[0]
    require(dev.platform == "tpu", f"no TPU: JAX runs on {dev.platform!r}")
    require(not _interpret(), "Pallas kernels would run in interpret mode")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _reset_counters() -> None:
    from repro.compiler import reset_stats as reset_kernel_stats
    from repro.engine import reset_stats

    reset_kernel_stats()
    reset_stats()


def _counters() -> dict:
    from repro.compiler import stats as kstats
    from repro.engine import stats as estats

    return {
        "kernels_built": kstats.kernels_built,
        "kernel_cache_hits": kstats.cache_hits,
        "fallbacks": kstats.fallbacks,
        "segments_fused": estats.segments_fused,
        "max_time_tile": estats.max_time_tile,
        "launches": estats.launches,
        "exchanges_per_step": estats.exchanges_per_step,
        "resident_runs": estats.resident_runs,
        "resident_dropped": estats.resident_dropped,
    }


def phase_explicit(cfg: HeatConfig, steps: int = STEPS) -> dict:
    """(b) Fig. 3 heat3d through ``wfa.make`` vs the jnp reference."""
    from repro.compiler import stats as kstats
    from repro.engine import plan
    from repro.engine.executor import single_runner
    from repro.engine.plan import transfer_kernels

    _reset_counters()
    opts = wfa.RunOptions(backend="pallas")
    wse, T = fig3_program(cfg, steps)
    # informational: compile time of the plan's runner, before ``make``
    # (which then finds the same program in the compile caches)
    p = plan(wse.program, opts)
    run = single_runner(p)
    env = {"T_n": jnp.asarray(make_field(cfg))}
    t0 = time.perf_counter()
    compiled = run.lower(env).compile()
    compile_s = time.perf_counter() - t0
    _reset_counters()
    t0 = time.perf_counter()
    out = wfa.make(wse, T, options=opts)
    first_s = time.perf_counter() - t0
    counters = _counters()
    ref = reference_heat(make_field(cfg), cfg.omega, steps)
    err = rel_err(out, ref)
    require(out.shape == ref.shape and np.all(np.isfinite(out)),
            "explicit result is not a finite field of the grid's shape")
    require(err <= RTOL, f"explicit rel err {err:.3e} > {RTOL:g}")
    require(kstats.fallbacks == 0, f"{kstats.fallbacks} interpreter fallbacks")

    # informational: steady time of the compiled runner
    jax.block_until_ready(compiled(env))
    env = {"T_n": jnp.asarray(make_field(cfg))}
    jax.block_until_ready(env)
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(env))
    step_us = (time.perf_counter() - t0) / steps * 1e6
    seg = p.segments[0]
    return {
        "rel_err": err,
        "time_tile": seg.time_tile,
        "segment": seg.kind,
        "resident_layout": p.layout.pad > 0,
        "mg_transfer_kernels": transfer_kernels(),
        "first_make_s": first_s,
        "compile_s": compile_s,
        "steady_step_us": step_us,
        **counters,
    }


def phase_solve(cfg: HeatConfig) -> dict:
    """(c) BTCS CG solve on the pallas backend, checked by a true residual."""
    from repro.compiler import stats as kstats

    _reset_counters()
    wse, T = record_implicit(cfg)
    T0 = np.array(T.init_data)
    t0 = time.perf_counter()
    x, info = wse.solve(T, method="cg", tol=cfg.tol, maxiter=cfg.maxiter,
                        options=wfa.RunOptions(backend="pallas"),
                        return_info=True)
    solve_s = time.perf_counter() - t0
    outcome = str(info.outcomes[0])
    res = btcs_residual(x, T0, cfg.omega)
    require(outcome == "CONVERGED", f"CG outcome {outcome}")
    require(np.all(np.isfinite(x)), "solve result is not finite")
    require(res <= SOLVE_RTOL, f"true relative residual {res:.3e} > {SOLVE_RTOL:g}")
    require(kstats.fallbacks == 0, f"{kstats.fallbacks} interpreter fallbacks")
    return {
        "outcome": outcome,
        "iterations": int(info.iterations[0]),
        "solver_residual": float(info.residual[0]),
        "true_rel_residual": res,
        "first_solve_s": solve_s,
        "fallbacks": kstats.fallbacks,
    }


def phase_service(cfg: HeatConfig, n_step: int = 4, steps: int = 16) -> dict:
    """(d) A warmed service serves step and solve requests at ``cfg``'s size."""
    from repro.compiler import stats as kstats
    from repro.service import (PlanSignature, SimulationService, SolveRequest,
                               StepRequest)

    _reset_counters()
    shape = (cfg.nx, cfg.ny, cfg.nz)
    step_sig = PlanSignature("heat3d", shape)
    solve_sig = PlanSignature("btcs_heat", shape)
    svc = SimulationService(workers=1, manifest=[step_sig, solve_sig],
                            default_chunk=8)
    t0 = time.perf_counter()
    svc.start()
    warm_s = time.perf_counter() - t0
    built = kstats.kernels_built
    try:
        t0 = time.perf_counter()
        tickets = [svc.submit(StepRequest(step_sig, steps=steps))
                   for _ in range(n_step)]
        tickets.append(svc.submit(SolveRequest(solve_sig, tol=cfg.tol,
                                               maxiter=cfg.maxiter)))
        results = [t.result(timeout=900) for t in tickets]
        serve_s = time.perf_counter() - t0
    finally:
        svc.stop()
    stats = [t.stats for t in tickets]
    ref = reference_heat(make_field(cfg), 0.1, steps)
    err = max(rel_err(r, ref) for r in results[:n_step])
    require(all(np.all(np.isfinite(r)) for r in results),
            "a served result is not finite")
    require(err <= RTOL, f"served heat3d rel err {err:.3e} > {RTOL:g}")
    require(stats[-1].outcome == "CONVERGED",
            f"served solve outcome {stats[-1].outcome!r}")
    retries = sum(s.retries for s in stats)
    degraded = sum(s.degraded for s in stats)
    require(retries == 0, f"{retries} retries")
    require(degraded == 0, f"{degraded} degraded requests")
    require(kstats.kernels_built == built,
            f"{kstats.kernels_built - built} kernels built after warm-up")
    require(kstats.fallbacks == 0, f"{kstats.fallbacks} interpreter fallbacks")
    return {
        "requests": len(tickets),
        "rel_err": err,
        "solve_iterations": stats[-1].iterations,
        "retries": retries,
        "degraded": degraded,
        "kernels_built_after_warmup": kstats.kernels_built - built,
        "warm_s": warm_s,
        "serve_s": serve_s,
        "p50_latency_s": float(np.median([s.latency_s for s in stats])),
    }


def phase_mesh(cfg: HeatConfig, devices, steps: int = STEPS) -> dict:
    """``--chips 4``: heat3d explicit and CG on a 2x2 mesh vs one device."""
    from repro.core.jaxcompat import make_mesh

    require(len(devices) == 4, f"need 4 devices, have {len(devices)}")
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
    one = wfa.RunOptions(backend="pallas")
    four = one.replace(mesh=mesh)

    wse, T = fig3_program(cfg, steps)
    single = wfa.make(wse, T, options=one)
    wse, T = fig3_program(cfg, steps)
    t0 = time.perf_counter()
    sharded = wfa.make(wse, T, options=four)
    make_s = time.perf_counter() - t0
    err = rel_err(sharded, single)
    require(np.all(np.isfinite(sharded)), "sharded explicit result not finite")
    require(err <= RTOL, f"sharded explicit rel err {err:.3e} > {RTOL:g}")

    kw = dict(method="cg", tol=cfg.tol, maxiter=cfg.maxiter, return_info=True)
    wse, T = record_implicit(cfg)
    x1, i1 = wse.solve(T, options=one, **kw)
    wse, T = record_implicit(cfg)
    t0 = time.perf_counter()
    x4, i4 = wse.solve(T, options=four, **kw)
    solve_s = time.perf_counter() - t0
    serr = rel_err(x4, x1)
    require(str(i4.outcomes[0]) == "CONVERGED",
            f"sharded CG outcome {i4.outcomes[0]}")
    require(serr <= RTOL, f"sharded solve rel err {serr:.3e} > {RTOL:g}")
    return {
        "brick": (cfg.nx // 2, cfg.ny // 2, cfg.nz),
        "explicit_rel_err": err,
        "solve_rel_err": serr,
        "iterations_1": int(i1.iterations[0]),
        "iterations_4": int(i4.iterations[0]),
        "sharded_make_s": make_s,
        "sharded_solve_s": solve_s,
    }


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(d) on chip 0; 4: the 2x2-mesh "
                         "heat3d comparison only")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    device = phase_device()
    cache = Path(enable_compile_cache())
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    say(f"(a) device_kind={device['kind']} platform={device['platform']} "
        f"count={device['count']} compile_cache={cache} "
        f"({warm} entries at start)")
    cfg = HeatConfig()
    say(f"    grid {cfg.nx}x{cfg.ny}x{cfg.nz} {cfg.dtype} "
        f"({cfg.cells:.3g} cells)")
    t_all = time.perf_counter()
    if args.chips == 4:
        require(device["count"] >= 4, f"--chips 4 needs 4 chips, have "
                f"{device['count']}")
        m = phase_mesh(cfg, jax.devices()[:4])
        say("(mesh) " + json.dumps(m))
    else:
        b = phase_explicit(cfg)
        say("(b) explicit " + json.dumps(b))
        c = phase_solve(cfg)
        say("(c) solve " + json.dumps(c))
        d = phase_service(cfg)
        say("(d) service " + json.dumps(d))
    say(f"    wall {time.perf_counter() - t_all:.1f} s (informational)")
    say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
