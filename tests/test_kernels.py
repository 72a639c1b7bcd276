"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [(4, 4, 4), (8, 8, 16), (16, 128, 8), (6, 10, 5), (8, 256, 32),
          (3, 7, 9)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32])
def test_stencil7_sweep(rng, shape, dtype):
    bx, by, nz = shape
    P = jnp.asarray(rng.normal(size=(bx + 2, by + 2, nz)).astype(dtype))
    out = ops.stencil7(P, 0.4, 0.1)
    expect = ref.affine_stencil_ref(P, 0.4, 0.1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("coeffs", [(1.0, -0.0625), (0.4, 0.1), (1.0, 0.0)])
def test_stencil7_coeffs(rng, coeffs):
    P = jnp.asarray(rng.normal(size=(10, 14, 12)).astype(np.float32))
    out = ops.stencil7(P, *coeffs)
    expect = ref.affine_stencil_ref(P, *coeffs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES[:4])
def test_spmv_dot_sweep(rng, shape):
    bx, by, nz = shape
    P = jnp.asarray(rng.normal(size=(bx + 2, by + 2, nz)).astype(np.float32))
    av, d = ops.spmv_hex_dot(P, 1.0, -0.0625)
    rav, rd = ref.spmv_dot_ref(P, 1.0, -0.0625)
    np.testing.assert_allclose(np.asarray(av), np.asarray(rav), atol=1e-5)
    np.testing.assert_allclose(float(d), float(rd), rtol=1e-4)


def test_spmv_matches_stencil(rng):
    P = jnp.asarray(rng.normal(size=(10, 130, 12)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(ops.spmv_hex(P, 1.0, -0.05)),
        np.asarray(ops.stencil7(P, 1.0, -0.05)), atol=1e-6)


# -- non-divisible grids × block shapes (block picker must fall back to a
#    divisor; coverage for the generalized fused path too) -------------------

ODD_SHAPES = [(5, 7, 3), (9, 13, 6), (7, 130, 12)]
BLOCKS = [(8, 128), (4, 32), (3, 5)]


@pytest.mark.parametrize("shape", ODD_SHAPES)
@pytest.mark.parametrize("block", BLOCKS)
def test_stencil7_odd_shapes_blocks(rng, shape, block):
    bx, by, nz = shape
    P = jnp.asarray(rng.normal(size=(bx + 2, by + 2, nz)).astype(np.float32))
    out = ops.stencil7(P, 0.4, 0.1, block=block)
    expect = ref.affine_stencil_ref(P, 0.4, 0.1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("shape", ODD_SHAPES[:2])
@pytest.mark.parametrize("block", BLOCKS)
def test_spmv_dot_odd_shapes_blocks(rng, shape, block):
    bx, by, nz = shape
    P = jnp.asarray(rng.normal(size=(bx + 2, by + 2, nz)).astype(np.float32))
    av, d = ops.spmv_hex_dot(P, 1.0, -0.0625, block=block)
    rav, rd = ref.spmv_dot_ref(P, 1.0, -0.0625)
    np.testing.assert_allclose(np.asarray(av), np.asarray(rav), atol=1e-5)
    np.testing.assert_allclose(float(d), float(rd), rtol=1e-4)


def _dots64(a, b, c, d):
    f = lambda x: np.asarray(x, np.float64).ravel()  # noqa: E731
    return np.array([f(a) @ f(b), f(c) @ f(d)])


@pytest.mark.parametrize("pattern", ["distinct", "cg", "pipecg"])
def test_dual_dot_blocks(rng, pattern):
    """The operand-sharing pairs the Krylov solvers pass, vs fp64 numpy."""
    r, w, z, v = [jnp.asarray(rng.normal(size=(12, 64, 4)).astype(np.float32))
                  for _ in range(4)]
    args = {"distinct": (r, w, z, v), "cg": (r, z, r, r),
            "pipecg": (r, r, w, r)}[pattern]
    out = ops.dual_dot(*args)
    assert out.shape == (2,) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), _dots64(*args), rtol=2e-4)


@pytest.mark.parametrize("shape", [(16, 64, 8), (4, 4, 4), (32, 128, 2)])
def test_dual_dot_sweep(rng, shape):
    a, b, c, d = [jnp.asarray(rng.normal(size=shape).astype(np.float32))
                  for _ in range(4)]
    out = ops.dual_dot(a, b, c, d)
    np.testing.assert_allclose(np.asarray(out), _dots64(a, b, c, d),
                               rtol=2e-4)


def test_dual_dot_zero():
    z = jnp.zeros((8, 128, 4), jnp.float32)
    out = ops.dual_dot(z, z, z, z)
    np.testing.assert_array_equal(np.asarray(out), np.zeros(2))


@pytest.mark.parametrize("shape,coords,meshdim", [
    ((8, 8, 8), (0, 0), (1, 1)),        # single brick = whole domain
    ((8, 16, 8), (1, 0), (2, 2)),       # interior-ish brick
    ((6, 10, 5), (1, 1), (2, 2)),       # bottom-right brick
])
def test_stencil_planes_sweep(rng, shape, coords, meshdim):
    """The fully-fused halo-plane kernel vs the padded-assembly oracle."""
    bx, by, nz = shape
    T = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    xlo = jnp.asarray(rng.normal(size=(1, by, nz)).astype(np.float32))
    xhi = jnp.asarray(rng.normal(size=(1, by, nz)).astype(np.float32))
    ylo = jnp.asarray(rng.normal(size=(bx, 1, nz)).astype(np.float32))
    yhi = jnp.asarray(rng.normal(size=(bx, 1, nz)).astype(np.float32))
    carr = jnp.asarray([[coords[0], coords[1]]], jnp.int32)
    nx, ny = meshdim[0] * bx, meshdim[1] * by
    out = ops.stencil7_planes(T, xlo, xhi, ylo, yhi, carr, 0.4, 0.1, nx, ny)
    expect = ref.stencil_planes_ref(T, xlo, xhi, ylo, yhi, carr, 0.4, 0.1,
                                    nx, ny)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5)
