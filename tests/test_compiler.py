"""The WFA program compiler: backend="pallas" vs the interpreter backends.

Covers the acceptance surface: agreement with backend="numpy" on the Fig. 3
heat program, the variable-coefficient diffusion program, and the
advection–diffusion example (off-axis taps); exactly one fused pallas_call
per ForLoop body (via the kernel cache counters); interpreter fallback for
non-affine bodies; and the normalized negative-start z slices.
"""
import os
import sys

import numpy as np
import pytest

from conftest import ftcs_oracle, heat_init
from repro.compiler import (LoweringError, Tap, clear_cache, lower_group,
                            lower_update, reset_stats, stats)
from repro.core import WSE_Array, WSE_For_Loop, WSE_Interface

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples"))
from advection_diffusion import build_advection_diffusion  # noqa: E402


def build_heat(T0, steps, c=0.1, name="T_n"):
    wse = WSE_Interface()
    center = 1.0 - 6.0 * c
    T = WSE_Array(name, init_data=T0)
    with WSE_For_Loop("t", steps):
        T[1:-1, 0, 0] = center * T[1:-1, 0, 0] + c * (
            T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0] + T[1:-1, 0, -1]
            + T[1:-1, -1, 0] + T[1:-1, 0, 1])
    return wse, T


def build_varcoef(T0, C0, steps):
    wse = WSE_Interface()
    T = WSE_Array("T_n", init_data=T0)
    C = WSE_Array("C_f", init_data=C0)
    with WSE_For_Loop("t", steps):
        T[1:-1, 0, 0] = T[1:-1, 0, 0] + C[1:-1, 0, 0] * (
            T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0] + T[1:-1, 0, -1]
            + T[1:-1, -1, 0] + T[1:-1, 0, 1] - 6.0 * T[1:-1, 0, 0])
    return wse, T


def unit_heat_init(shape=(10, 12, 14)):
    """Fig. 3 profile rescaled to O(1) so atol=1e-4 is meaningful."""
    return heat_init(shape) / 500.0


# -- backend agreement (acceptance: pallas == numpy to 1e-4) -----------------

def test_pallas_matches_numpy_fig3_heat():
    T0 = unit_heat_init()
    wse, T = build_heat(T0, steps=7)
    a = wse.make(answer=T, backend="pallas")
    wse, T = build_heat(T0, steps=7)
    b = wse.make(answer=T, backend="numpy")
    np.testing.assert_allclose(a, b, atol=1e-4)
    np.testing.assert_allclose(a, ftcs_oracle(T0, 0.1, 7), atol=1e-4)


def test_pallas_matches_numpy_fig3_heat_kelvin_scale():
    # the paper's 300-500 K field; 2e-4 matches the seed's jit-vs-numpy bound
    T0 = heat_init()
    wse, T = build_heat(T0, steps=7)
    a = wse.make(answer=T, backend="pallas")
    wse, T = build_heat(T0, steps=7)
    b = wse.make(answer=T, backend="numpy")
    np.testing.assert_allclose(a, b, atol=2e-4)


def test_pallas_matches_numpy_variable_coefficient(rng):
    T0 = unit_heat_init((8, 9, 10))
    C0 = rng.uniform(0.02, 0.15, size=T0.shape).astype(np.float32)
    wse, T = build_varcoef(T0, C0, steps=4)
    a = wse.make(answer=T, backend="pallas")
    wse, T = build_varcoef(T0, C0, steps=4)
    b = wse.make(answer=T, backend="numpy")
    np.testing.assert_allclose(a, b, atol=1e-4)


def test_pallas_matches_numpy_advection_diffusion(rng):
    T0 = rng.uniform(0.0, 1.0, size=(9, 11, 8)).astype(np.float32)
    wse, T = build_advection_diffusion(T0, steps=6)
    a = wse.make(answer=T, backend="pallas")
    wse, T = build_advection_diffusion(T0, steps=6)
    b = wse.make(answer=T, backend="numpy")
    np.testing.assert_allclose(a, b, atol=1e-4)


def test_pallas_boundaries_pinned():
    T0 = heat_init()
    wse, T = build_heat(T0, steps=10)
    out = wse.make(answer=T, backend="pallas")
    np.testing.assert_array_equal(out[0, :, :], T0[0, :, :])
    np.testing.assert_array_equal(out[-1, :, :], T0[-1, :, :])
    np.testing.assert_array_equal(out[:, 0, :], T0[:, 0, :])
    np.testing.assert_array_equal(out[:, :, 0], T0[:, :, 0])
    np.testing.assert_array_equal(out[:, :, -1], T0[:, :, -1])


# -- fusion accounting (acceptance: one fused pallas_call per loop body) -----

def test_fig3_compiles_to_one_fused_kernel():
    T0 = unit_heat_init()
    reset_stats()
    clear_cache()
    wse, T = build_heat(T0, steps=3)
    wse.make(answer=T, backend="pallas")
    assert stats.groups_fused == 1       # one ForLoop body → one fused step
    assert stats.kernels_built == 1      # exactly one pallas_call emitted
    assert stats.fallbacks == 0


def test_kernel_cache_reuses_compiled_program():
    T0 = unit_heat_init()
    reset_stats()
    clear_cache()
    wse, T = build_heat(T0, steps=3)
    wse.make(answer=T, backend="pallas")
    wse, T = build_heat(T0, steps=3)
    wse.make(answer=T, backend="pallas")
    assert stats.groups_fused == 2
    assert stats.kernels_built == 1      # second make served from the cache
    assert stats.cache_hits == 1


def test_multi_op_loop_body_fuses_into_one_kernel(rng):
    """Two coupled fields updated in one loop body → still one pallas_call
    (the second op reads the first's update only at dx = dy = 0)."""
    A0 = rng.uniform(0.0, 1.0, size=(8, 8, 6)).astype(np.float32)
    B0 = rng.uniform(0.0, 1.0, size=(8, 8, 6)).astype(np.float32)

    def build():
        wse = WSE_Interface()
        A = WSE_Array("A", init_data=A0)
        B = WSE_Array("B", init_data=B0)
        with WSE_For_Loop("t", 4):
            A[1:-1, 0, 0] = A[1:-1, 0, 0] + 0.1 * (
                B[1:-1, 1, 0] + B[1:-1, -1, 0] - 2.0 * B[1:-1, 0, 0])
            B[1:-1, 0, 0] = B[1:-1, 0, 0] + 0.05 * A[1:-1, 0, 0]
        return wse, A, B

    reset_stats()
    clear_cache()
    wse, A, B = build()
    a = wse.make(answer=A, backend="pallas")
    assert stats.kernels_built == 1 and stats.fallbacks == 0
    wse, A, B = build()
    b = wse.make(answer=A, backend="numpy")
    np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("body", ["heat", "advdiff", "coupled"])
def test_lane_mask_substep_matches_splice(rng, body):
    """The fused kernel's two Z formulations — lane rotations + a lane mask
    (what Mosaic compiles) and window slices + an in-place splice (what
    the interpreter runs) — give the same sub-step, bit for bit."""
    import jax

    from repro.core.program import _group_ops
    from repro.kernels.fused import _apply_updates

    X0 = rng.uniform(0.0, 1.0, size=(9, 11, 8)).astype(np.float32)
    if body == "heat":
        wse, _ = build_heat(X0, steps=2)
    elif body == "advdiff":
        wse, _ = build_advection_diffusion(X0, steps=2)
    else:
        wse = WSE_Interface()
        A = WSE_Array("A", init_data=X0)
        B = WSE_Array("B", init_data=X0[::-1].copy())
        with WSE_For_Loop("t", 2):
            A[1:-1, 0, 0] = A[1:-1, 0, 0] + 0.1 * (
                B[1:-1, 1, 0] + B[1:-1, -1, 0] - 2.0 * B[2:, 0, 0])
            B[1:-1, 0, 0] = B[1:-1, 0, 0] + 0.05 * A[:-2, 0, 0]
    (_, ops), = [g for g in _group_ops(wse.program) if g[0] is not None]
    wse.__exit__()
    group = lower_group(ops)
    h = group.halo
    cur = {n: rng.uniform(0.0, 1.0, size=(9, 11, 8)).astype(np.float32)
           for n in wse.program.fields}

    def substep(interpret):
        return jax.jit(lambda c: _apply_updates(
            group.updates, c, h, 9 - 2 * h, 11 - 2 * h, 3, 5, 16, 16, True,
            interpret))(cur)

    lane, splice = substep(False), substep(True)
    for n in cur:
        np.testing.assert_array_equal(np.asarray(lane[n]),
                                      np.asarray(splice[n]))


# -- interpreter fallback ----------------------------------------------------

def test_non_affine_body_falls_back_to_interpreter(rng):
    T0 = rng.uniform(0.5, 1.0, size=(8, 8, 6)).astype(np.float32)

    def build():
        wse = WSE_Interface()
        T = WSE_Array("T_nl", init_data=T0)
        with WSE_For_Loop("t", 3):
            T[1:-1, 0, 0] = T[1:-1, 0, 0] * T[1:-1, 0, 0] * T[1:-1, 1, 0]
        return wse, T

    reset_stats()
    wse, T = build()
    a = wse.make(answer=T, backend="pallas")
    assert stats.fallbacks == 1 and stats.kernels_built == 0
    assert "non-affine" in stats.fallback_reasons[0]
    wse, T = build()
    b = wse.make(answer=T, backend="numpy")
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_division_by_field_falls_back(rng):
    T0 = rng.uniform(0.5, 1.0, size=(6, 6, 5)).astype(np.float32)

    def build():
        wse = WSE_Interface()
        T = WSE_Array("T_div", init_data=T0)
        with WSE_For_Loop("t", 2):
            T[1:-1, 0, 0] = T[1:-1, 0, 0] / (T[1:-1, 1, 0] + 2.0)
        return wse, T

    reset_stats()
    wse, T = build()
    a = wse.make(answer=T, backend="pallas")
    assert stats.fallbacks == 1
    wse, T = build()
    b = wse.make(answer=T, backend="numpy")
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_cross_tile_raw_hazard_falls_back(rng):
    """Second op reads the first op's written field through (dx, dy) ≠ 0 —
    unfusable read-after-write; the interpreter fallback must still agree."""
    A0 = rng.uniform(0.0, 1.0, size=(8, 8, 6)).astype(np.float32)

    def build():
        wse = WSE_Interface()
        A = WSE_Array("A", init_data=A0)
        B = WSE_Array("B", init_data=A0.copy())
        with WSE_For_Loop("t", 3):
            A[1:-1, 0, 0] = 0.5 * A[1:-1, 0, 0]
            B[1:-1, 0, 0] = B[1:-1, 0, 0] + 0.1 * A[1:-1, 1, 0]
        return wse, B

    reset_stats()
    wse, B = build()
    a = wse.make(answer=B, backend="pallas")
    assert stats.fallbacks == 1
    assert "cross-tile" in stats.fallback_reasons[0]
    wse, B = build()
    b = wse.make(answer=B, backend="numpy")
    np.testing.assert_allclose(a, b, atol=1e-5)


# -- normalized z slices (negative starts) -----------------------------------

@pytest.mark.parametrize("backend", ["numpy", "jit", "pallas"])
def test_negative_start_zslice_backends_agree(backend, rng):
    """On an n=10 column, T[-9:-1, 0, 0] IS the center slice T[1:-1, 0, 0];
    the negative-start spelling must evaluate identically on every backend —
    the record-time slice.indices normalization (the old _slice_delta took
    -9 - 1 = -10 as a z shift for this slice pair)."""
    T0 = rng.uniform(0.0, 1.0, size=(8, 9, 10)).astype(np.float32)

    def build(neg):
        center = slice(-9, -1) if neg else slice(1, -1)
        wse = WSE_Interface()
        T = WSE_Array("T_n", init_data=T0)
        with WSE_For_Loop("t", 4):
            T[1:-1, 0, 0] = 0.5 * T[center, 0, 0] + 0.25 * (
                T[2:, 0, 0] + T[:-2, 0, 0])
        return wse, T

    wse, T = build(neg=True)
    a = wse.make(answer=T, backend=backend)
    wse, T = build(neg=False)
    b = wse.make(answer=T, backend="numpy")
    np.testing.assert_allclose(a, b, atol=2e-4)


# -- IR unit checks ----------------------------------------------------------

def _record_one(build_expr):
    wse = WSE_Interface()
    try:
        T = WSE_Array("T_ir", shape=(6, 6, 8))
        build_expr(T)
        return wse.program.ops
    finally:
        wse.__exit__()


def test_lowering_canonicalizes_fig3_to_seven_taps():
    ops = _record_one(lambda T: T.__setitem__(
        (slice(1, -1), 0, 0),
        0.4 * T[1:-1, 0, 0] + 0.1 * (
            T[2:, 0, 0] + T[:-2, 0, 0] + T[1:-1, 1, 0] + T[1:-1, 0, -1]
            + T[1:-1, -1, 0] + T[1:-1, 0, 1])))
    u = lower_update(ops[0])
    assert u.z0 == 1 and u.zlen == 6 and u.const == 0.0
    taps = {taps[0]: c for c, taps in u.terms}
    assert len(taps) == 7
    assert taps[Tap("T_ir", 0, 0, 0)] == pytest.approx(0.4)
    for tap in [Tap("T_ir", 1, 0, 0), Tap("T_ir", -1, 0, 0),
                Tap("T_ir", 0, 1, 0), Tap("T_ir", 0, -1, 0),
                Tap("T_ir", 0, 0, 1), Tap("T_ir", 0, 0, -1)]:
        assert taps[tap] == pytest.approx(0.1)


def test_lowering_folds_constants_and_merges_like_terms():
    ops = _record_one(lambda T: T.__setitem__(
        (slice(1, -1), 0, 0),
        (T[1:-1, 0, 0] * 0.5 + 0.5 * T[1:-1, 0, 0]) - 0.0 * T[1:-1, 1, 0]
        + (1.0 + 2.0)))
    u = lower_update(ops[0])
    assert u.const == pytest.approx(3.0)
    assert len(u.terms) == 1                    # like terms merged, 0·T dropped
    (coeff, taps), = u.terms
    assert taps == (Tap("T_ir", 0, 0, 0),) and coeff == pytest.approx(1.0)


def test_lowering_halo_radius_from_offsets():
    ops = _record_one(lambda T: T.__setitem__(
        (slice(1, -1), 0, 0), T[1:-1, 1, 1] + T[1:-1, -1, -1]))
    g = lower_group(ops)
    assert g.halo == 1
    assert g.fields_written() == ("T_ir",)


def test_lowering_rejects_degree_three():
    ops = _record_one(lambda T: T.__setitem__(
        (slice(1, -1), 0, 0),
        T[1:-1, 0, 0] * T[1:-1, 0, 0] * T[1:-1, 0, 0]))
    with pytest.raises(LoweringError):
        lower_group(ops)
