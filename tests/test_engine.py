"""The unified execution engine: planner, executor, temporal blocking.

Covers the PR-3 acceptance surface: every ``make`` backend routes through
``engine.plan``/``engine.execute`` (one dispatch point), a k=4 time-tiled
heat3d run ftol-matches the untiled run while the engine's communication
accounting shows one wrap pad / halo exchange per k steps, the remainder
path (``n % k``), clamping of illegal tile factors with a logged reason,
the untiled interpreter fallback for non-affine bodies, and — property-based
— that k-step tiled execution matches k single steps for random affine
programs.  (The sharded k-tiled run lives in tests/test_sharded.py: it
needs the 4-device subprocess.)
"""

import numpy as np
import pytest

from conftest import ftcs_oracle, heat_init
from repro.compiler import reset_stats as compiler_reset
from repro.compiler import stats as compiler_stats
from repro.configs.heat3d import HeatConfig, make_field
from repro.core import WSE_Array, WSE_For_Loop, WSE_Interface
from repro.engine import BACKENDS, plan, reset_stats, spans, stats


def build_heat(T0, steps, c=0.1):
    wse = WSE_Interface()
    center = 1.0 - 6.0 * c
    T = WSE_Array("T_n", init_data=T0)
    with WSE_For_Loop("t", steps):
        T[1:-1, 0, 0] = center * T[1:-1, 0, 0] + c * (
            T[2:, 0, 0]
            + T[:-2, 0, 0]
            + T[1:-1, 1, 0]
            + T[1:-1, 0, -1]
            + T[1:-1, -1, 0]
            + T[1:-1, 0, 1]
        )
    return wse, T


# -- planner routing (acceptance: no per-layer backend ladders) ---------------


@pytest.mark.parametrize("backend", ["numpy", "jit", "pallas"])
def test_every_backend_routes_through_the_planner(backend):
    T0 = heat_init()
    reset_stats()
    wse, T = build_heat(T0, steps=3)
    out = wse.make(answer=T, backend=backend)
    assert stats.plans_built == 1
    np.testing.assert_allclose(out, ftcs_oracle(T0, 0.1, 3), atol=2e-4)


def test_plan_schedules_fused_vs_interp_segments():
    T0 = heat_init()
    reset_stats()
    wse, T = build_heat(T0, steps=4)
    try:
        p = plan(wse.program, backend="pallas")
    finally:
        wse.__exit__()
    assert [s.kind for s in p.segments] == ["fused"]
    assert stats.segments_fused == 1 and stats.segments_interp == 0
    reset_stats()
    wse, T = build_heat(T0, steps=4)
    try:
        p = plan(wse.program, backend="jit")
    finally:
        wse.__exit__()
    assert [s.kind for s in p.segments] == ["interp"]


def test_unknown_backend_rejected():
    T0 = heat_init()
    wse, T = build_heat(T0, steps=2)
    with pytest.raises(ValueError, match="unknown backend"):
        wse.make(answer=T, backend="cerebras")
    assert "cerebras" not in BACKENDS


def test_solver_operator_application_dispatches_through_engine():
    from repro.solver import record_btcs

    reset_stats()
    wse, T = record_btcs(heat_init(), 0.1)
    x = wse.solve(T, method="cg", backend="pallas", tol=1e-6)
    # operator + rhs bodies both obtained from engine.compile_body
    assert stats.bodies_compiled >= 2
    assert np.isfinite(x).all()


# -- temporal blocking (acceptance: one exchange per k steps, ftol match) -----


def test_heat3d_k4_tiled_matches_untiled_one_pad_per_4_steps():
    cfg = HeatConfig().smoke()  # 16 x 16 x 12 heat3d grid
    T0 = make_field(cfg)
    steps = 8

    reset_stats()
    wse, T = build_heat(T0, steps, c=cfg.omega)
    base = wse.make(answer=T, backend="pallas", time_tile=1)
    assert stats.exchanges_per_step == 1.0 and stats.tiles_fused == 0

    reset_stats()
    wse, T = build_heat(T0, steps, c=cfg.omega)
    tiled = wse.make(answer=T, backend="pallas", time_tile=4)
    # one wrap pad (the single-device exchange analogue) per 4 steps
    assert stats.exchanges_per_step == pytest.approx(0.25)
    assert stats.tiles_fused == 2 and stats.max_time_tile == 4
    (run,) = [s for s in spans() if s[0] == "wfa.engine.execute"]
    assert stats.steps_run == steps and run[2] > run[1]
    # ftol match: identical arithmetic per sub-step; XLA FMA fusion may
    # round differently at the last ulp (on the ~500 K field that is ~6e-5)
    np.testing.assert_allclose(tiled, base, atol=1e-3)
    np.testing.assert_allclose(tiled, ftcs_oracle(T0, cfg.omega, steps), atol=2e-3)


def test_remainder_steps_run_untiled():
    T0 = heat_init()
    reset_stats()
    wse, T = build_heat(T0, steps=7)
    out = wse.make(answer=T, backend="pallas", time_tile=4)
    # 7 = 1 tile of 4 + 3 untiled remainder launches -> 4 pads, not 7
    assert stats.tiles_fused == 1 and stats.launches == 4
    assert stats.exchanges == 4 and stats.steps_run == 7
    np.testing.assert_allclose(out, ftcs_oracle(T0, 0.1, 7), atol=2e-4)


def test_illegal_tile_factor_clamped_with_logged_reason():
    T0 = heat_init()  # trip count 6 < requested 64
    reset_stats()
    wse, T = build_heat(T0, steps=6)
    out = wse.make(answer=T, backend="pallas", time_tile=64)
    assert stats.tile_reasons and "clamped" in stats.tile_reasons[0]
    assert stats.max_time_tile <= 6
    np.testing.assert_allclose(out, ftcs_oracle(T0, 0.1, 6), atol=2e-4)


def test_time_tile_on_interpreter_backend_noted_not_silent():
    T0 = heat_init()
    reset_stats()
    wse, T = build_heat(T0, steps=4)
    out = wse.make(answer=T, backend="jit", time_tile=4)
    assert stats.tile_reasons and "ignored" in stats.tile_reasons[0]
    assert stats.max_time_tile == 1
    np.testing.assert_allclose(out, ftcs_oracle(T0, 0.1, 4), atol=2e-4)


def test_auto_tile_prefers_divisors_of_the_trip_count():
    T0 = np.asarray(heat_init((24, 24, 8)))
    reset_stats()
    wse, T = build_heat(T0, steps=8)
    # auto: 8 divides 8 but 4*8*h > 24 (halo-vs-brick bound) -> k = 4
    wse.make(answer=T, backend="pallas")
    assert stats.max_time_tile == 4
    reset_stats()
    wse, T = build_heat(T0, steps=7)
    wse.make(answer=T, backend="pallas")  # auto: no power-of-2 divisor of 7
    assert stats.max_time_tile == 1


def test_non_affine_body_falls_back_untiled(rng):
    T0 = rng.uniform(0.5, 1.0, size=(8, 8, 6)).astype(np.float32)

    def build():
        wse = WSE_Interface()
        T = WSE_Array("T_nl", init_data=T0)
        with WSE_For_Loop("t", 4):
            T[1:-1, 0, 0] = T[1:-1, 0, 0] * T[1:-1, 0, 0] * T[1:-1, 1, 0]
        return wse, T

    reset_stats()
    compiler_reset()
    wse, T = build()
    a = wse.make(answer=T, backend="pallas", time_tile=4)
    assert stats.segments_interp == 1 and stats.max_time_tile == 1
    assert compiler_stats.fallbacks == 1
    wse, T = build()
    b = wse.make(answer=T, backend="numpy")
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_tile_group_legality_bounds():
    from repro.compiler import LoweringError, lower_group, tile_group

    wse, T = build_heat(heat_init(), steps=4)
    try:
        group = lower_group(wse.program.ops)
    finally:
        wse.__exit__()
    assert tile_group(group, 3).halo == 3 * group.halo
    with pytest.raises(LoweringError):
        tile_group(group, 0)
    with pytest.raises(LoweringError):
        tile_group(group, 9, n_steps=4)
    with pytest.raises(LoweringError):
        tile_group(group, 5, brick_xy=(4, 4))  # halo 5 > brick 4


# -- property: k tiled steps == k single steps (random affine programs) -------


def check_tiled_matches_k_single_steps(shape, seed, n_taps, steps, k, varcoef):
    """k-step tiled pallas execution == k single interpreter steps, and the
    engine's pad/exchange count drops k× — for one random affine program."""
    rng = np.random.default_rng(seed)
    T0 = rng.uniform(0.0, 1.0, size=shape).astype(np.float32)
    C0 = rng.uniform(0.5, 1.5, size=shape).astype(np.float32)
    offsets = [
        (dz, dx, dy)
        for dz in (-1, 0, 1)
        for dx in (-1, 0, 1)
        for dy in (-1, 0, 1)
    ]
    picks = rng.choice(len(offsets), size=n_taps, replace=False)
    taps = [offsets[i] for i in picks]
    coeffs = rng.uniform(-0.15, 0.15, size=n_taps)
    zs = {-1: slice(None, -2), 0: slice(1, -1), 1: slice(2, None)}

    def build():
        wse = WSE_Interface()
        T = WSE_Array("T_p", init_data=T0)
        C = WSE_Array("C_p", init_data=C0)
        expr = 0.5 * T[1:-1, 0, 0]
        for (dz, dx, dy), c in zip(taps, coeffs):
            term = float(c) * T[zs[dz], dx, dy]
            if varcoef:
                term = C[1:-1, 0, 0] * term
            expr = expr + term
        with WSE_For_Loop("t", steps):
            T[1:-1, 0, 0] = expr
        return wse, T

    wse, T = build()
    ref = wse.make(answer=T, backend="jit")  # k single interpreter steps
    reset_stats()
    wse, T = build()
    out = wse.make(answer=T, backend="pallas", time_tile=k)
    np.testing.assert_allclose(out, ref, atol=1e-4)

    halo = max(max(abs(dx), abs(dy)) for _, dx, dy in taps + [(0, 0, 0)])
    k_eff = min(k, steps)
    expected = (steps // k_eff + steps % k_eff) if halo else 0
    assert stats.exchanges == expected  # one pad per tile, k x fewer
    assert stats.steps_run == steps


@pytest.mark.parametrize(
    "shape, seed, n_taps, steps, k, varcoef",
    [
        ((8, 9, 6), 0, 3, 8, 4, False),
        ((7, 10, 5), 1, 5, 6, 2, True),
        ((6, 6, 4), 2, 1, 5, 3, False),  # remainder + maybe z-only body
        ((10, 8, 7), 3, 4, 4, 4, True),
    ],
)
def test_tiled_matches_k_single_steps_fixed_cases(
    shape, seed, n_taps, steps, k, varcoef
):
    """Fixed draws of the property below — run even without hypothesis."""
    check_tiled_matches_k_single_steps(shape, seed, n_taps, steps, k, varcoef)


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional test extra
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @given(
        shape=st.tuples(
            st.integers(6, 10), st.integers(6, 10), st.integers(4, 7)
        ),
        seed=st.integers(0, 10**6),
        n_taps=st.integers(1, 5),
        steps=st.integers(2, 8),
        k=st.integers(2, 4),
        varcoef=st.booleans(),
    )
    @settings(deadline=None, max_examples=15)
    def test_tiled_matches_k_single_steps_random_affine(
        shape, seed, n_taps, steps, k, varcoef
    ):
        check_tiled_matches_k_single_steps(shape, seed, n_taps, steps, k, varcoef)
