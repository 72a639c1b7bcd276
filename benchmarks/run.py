"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  * explicit_scaling    — Fig. 4a / Eq. 6 / Eqs. 4–5
  * implicit_scaling    — Fig. 4b / Eq. 16 / Eqs. 13–15 / §3.2.2 ratio
  * implicit_solve      — wfa.solve: compiled operator + Krylov loop
  * mg_poisson          — solver convergence: mg vs CG/BiCGSTAB, 3 sizes
  * time_tiling         — engine temporal blocking: k steps per exchange
  * reduction           — Eq. 17 / §3.2.2 dot-product analysis
  * distributed_model   — Table 1 / Table 2 / Eq. 12 / §5 headline speedups
  * kernels_bench       — Fig. 3 fused-RPC comparison + Pallas kernels
  * service_throughput  — serving layer: requests/sec, tail latency,
                          cache-hit rate, fault restore-and-continue
  * ensemble_throughput — batched ensemble execution: members/sec at
                          micro-batch widths 1/8/64 (gates the B=64 ≥ 5×
                          speedup and zero steady-state compiles)
  * adjoint_inverse     — differentiable solves: gradient/forward cost
                          ratio via the IFT adjoint (symmetric CG reuses
                          the forward kernel; BiCGSTAB row is the
                          inverse-diffusivity misfit gradient)
  * health_overhead     — explicit-path sentinel cost: guarded
                          (``check_finite=N``) vs unguarded steady-state
                          stepping, interleaved best-of (gates ≤2%)

Usage::

    python benchmarks/run.py [--json OUT.json] [--warmup N] [--repeats N]
                             [--check-fallbacks] [case ...]

``--json`` additionally writes the emitted rows as a JSON document — the
perf-trajectory artifact CI uploads per PR.  ``--warmup``/``--repeats``
override the harness-wide timing counts (rows report *best-of* over the
repeats — see :mod:`benchmarks.common` for why the median was retired).
``--check-fallbacks`` exits nonzero if any emitted row reports interpreter
fallbacks — the CI smoke gate keeping every pallas case on the fused path.
``--check-tiling`` exits nonzero if the time_tiling case's steady-state k=2
or k=4 row is slower than its k=1 row — temporal blocking must never lose
to untiled stepping (the cost model guarantees it by construction for
model-driven picks; this gates the measured reality).
``--check-health`` exits nonzero if any ``health_guard_on`` row reports
more than 2% per-step overhead against its unguarded baseline — arming the
explicit-path sentinel must stay effectively free at the chunk granule.
"""

from __future__ import annotations

import argparse
import json
import platform
import re
import sys


def main() -> None:
    from benchmarks import (
        adjoint_inverse,
        common,
        distributed_model,
        ensemble_throughput,
        explicit_scaling,
        health_overhead,
        implicit_scaling,
        implicit_solve,
        kernels_bench,
        mg_poisson,
        reduction,
        service_throughput,
        time_tiling,
    )
    from benchmarks.common import RESULTS

    mods = {
        "explicit_scaling": explicit_scaling,
        "implicit_scaling": implicit_scaling,
        "implicit_solve": implicit_solve,
        "mg_poisson": mg_poisson,
        "time_tiling": time_tiling,
        "reduction": reduction,
        "distributed_model": distributed_model,
        "kernels_bench": kernels_bench,
        "service_throughput": service_throughput,
        "ensemble_throughput": ensemble_throughput,
        "adjoint_inverse": adjoint_inverse,
        "health_overhead": health_overhead,
    }
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--json", metavar="PATH", default=None, help="also write emitted rows as JSON"
    )
    ap.add_argument(
        "--warmup",
        type=int,
        default=None,
        metavar="N",
        help="untimed calls before timing each row",
    )
    ap.add_argument(
        "--repeats",
        type=int,
        default=None,
        metavar="N",
        help="timed calls per row (best-of reported)",
    )
    ap.add_argument(
        "--check-fallbacks",
        action="store_true",
        help="fail if any row reports interpreter fallbacks",
    )
    ap.add_argument(
        "--check-tiling",
        action="store_true",
        help="fail if time_tiling k=2/k=4 rows lose to k=1",
    )
    ap.add_argument(
        "--check-health",
        action="store_true",
        help="fail if any health_guard_on row exceeds 2% overhead",
    )
    ap.add_argument(
        "cases",
        nargs="*",
        metavar="case",
        help=f"benchmark cases to run (default: all of {list(mods)})",
    )
    args = ap.parse_args()
    unknown = [c for c in args.cases if c not in mods]
    if unknown:
        ap.error(f"unknown case(s) {unknown}; choose from {list(mods)}")
    if args.warmup is not None and args.warmup < 0:
        ap.error("--warmup must be >= 0")
    if args.repeats is not None and args.repeats < 1:
        ap.error("--repeats must be >= 1")
    common.configure(warmup=args.warmup, repeats=args.repeats)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")
    for name, mod in mods.items():
        if args.cases and name not in args.cases:
            continue
        print(f"# --- {name} ---")
        mod.run()

    if args.json:
        import jax

        doc = {
            "cases": args.cases or list(mods),
            "backend": jax.default_backend(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "rows": RESULTS,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"# wrote {len(RESULTS)} rows to {args.json}")

    # gate AFTER the JSON dump: a fallback regression must still leave the
    # per-row artifact behind — it is what diagnoses which case fell back
    if args.check_fallbacks:
        from repro.compiler import stats as compiler_stats

        bad = [
            r
            for r in RESULTS
            for m in [re.search(r"fallbacks=(\d+)", str(r["derived"]))]
            if m and int(m.group(1)) > 0
        ]
        for r in bad:
            print(f"# FALLBACKS in {r['name']}: {r['derived']}", file=sys.stderr)
        # rows without a fallbacks= field still count via the process-wide
        # compiler counter, so un-instrumented cases cannot regress silently
        if compiler_stats.fallbacks > 0 and not bad:
            print(
                f"# FALLBACKS: {compiler_stats.fallbacks} across the run "
                f"(reasons: {compiler_stats.fallback_reasons[-3:]})",
                file=sys.stderr,
            )
        if bad or compiler_stats.fallbacks > 0:
            sys.exit(1)
        print("# fallbacks=0 in every instrumented row and process-wide")

    if args.check_tiling:
        rows = {r["name"]: float(r["us_per_call"]) for r in RESULTS}
        base = rows.get("time_tiling_k1")
        if base is None:
            print("# --check-tiling: no time_tiling_k1 row emitted", file=sys.stderr)
            sys.exit(1)
        losers = [
            (n, rows[n])
            for n in ("time_tiling_k2", "time_tiling_k4")
            if n in rows and rows[n] > base
        ]
        for n, us in losers:
            print(
                f"# TILING REGRESSION: {n}={us:.2f}us/step > k1={base:.2f}us/step",
                file=sys.stderr,
            )
        if losers:
            sys.exit(1)
        print(f"# tiling holds: k2/k4 <= k1 ({base:.2f}us/step)")

    if args.check_health:
        over = [
            (r["name"], float(m.group(1)))
            for r in RESULTS
            if str(r["name"]).startswith("health_guard_on")
            for m in [re.search(r"overhead_pct=(-?[\d.]+)", str(r["derived"]))]
            if m and float(m.group(1)) > 2.0
        ]
        rows = [r for r in RESULTS if str(r["name"]).startswith("health_guard_on")]
        if not rows:
            print("# --check-health: no health_guard_on row emitted", file=sys.stderr)
            sys.exit(1)
        for n, pct in over:
            print(
                f"# SENTINEL OVERHEAD: {n} costs {pct:.2f}% > 2% budget",
                file=sys.stderr,
            )
        if over:
            sys.exit(1)
        print(f"# sentinel budget holds: {len(rows)} guarded rows <= 2%")


if __name__ == "__main__":
    main()
