"""Jitted public wrappers around the Pallas kernels.

On a TPU backend the kernels compile via Mosaic; on any other backend (the
CPU unit tests) they execute under ``interpret=True`` so the same call sites
work everywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import spmv as _spmv
from repro.kernels import stencil7 as _stencil7


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def stencil7(P, c_diag: float, c_off: float, block=(8, 128)):
    """(bx+2, by+2, Z) halo-padded brick → fused affine 7-point stencil."""
    return _stencil7.affine_stencil(P, float(c_diag), float(c_off),
                                    block=block, interpret=_interpret())


def stencil7_planes(T, xlo, xhi, ylo, yhi, coords, c_diag, c_off,
                    nx: int, ny: int, block=(8, 128)):
    """Fully-fused FTCS step (unpadded brick + halo planes + in-kernel moat).

    The optimized explicit path: no pad-concat, no masking pass — see
    EXPERIMENTS.md §Perf (heat explicit iterations).
    """
    return _stencil7.stencil_planes(T, xlo, xhi, ylo, yhi, coords,
                                    float(c_diag), float(c_off), nx, ny,
                                    block=block, interpret=_interpret())


def spmv_hex(P, c_diag: float, c_off: float, block=(8, 128)):
    """SpMV only (discards the fused dot) — used by the CG operator."""
    av, _ = _spmv.spmv_dot(P, float(c_diag), float(c_off), block=block,
                           interpret=_interpret())
    return av


def spmv_hex_dot(P, c_diag: float, c_off: float, block=(8, 128)):
    """Fused SpMV + brick-local p·Ap.  Returns (Ap, scalar)."""
    av, partials = _spmv.spmv_dot(P, float(c_diag), float(c_off), block=block,
                                  interpret=_interpret())
    return av, jnp.sum(partials, dtype=jnp.float32)


def dual_dot(a, b, c, d):
    """Brick-local pair of dot products: ``jnp.stack([a·b, c·d])``.

    Plain jnp on purpose: XLA fuses the two reductions into one
    multi-output fusion that reads each distinct operand once — the
    solvers' pairs share operands (``(r, z, r, r)``, ``(r, r, w, r)``), so
    that single sweep moves less HBM than a kernel taking four operands.
    Accumulates in at least fp32 (fp64 operands stay fp64).
    """
    acc = jnp.promote_types(a.dtype, jnp.float32)
    return jnp.stack([jnp.sum(a * b, dtype=acc), jnp.sum(c * d, dtype=acc)])
