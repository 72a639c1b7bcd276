"""Pure-jnp oracles for every Pallas kernel (the allclose reference)."""
from __future__ import annotations

import jax.numpy as jnp


def affine_stencil_ref(P, c_diag: float, c_off: float):
    """Oracle for kernels.stencil7.affine_stencil."""
    c = P[1:-1, 1:-1, :]
    s = (P[:-2, 1:-1, :] + P[2:, 1:-1, :]
         + P[1:-1, :-2, :] + P[1:-1, 2:, :])
    zp = jnp.concatenate([c[:, :, 1:], c[:, :, -1:]], axis=2)
    zm = jnp.concatenate([c[:, :, :1], c[:, :, :-1]], axis=2)
    return c_diag * c + c_off * (s + zp + zm)


def spmv_dot_ref(P, c_diag: float, c_off: float):
    """Oracle for kernels.spmv.spmv_dot — returns (Ap, scalar p·Ap)."""
    av = affine_stencil_ref(P, c_diag, c_off)
    c = P[1:-1, 1:-1, :]
    return av, jnp.sum(c * av, dtype=jnp.float32)


def stencil_planes_ref(T, xlo, xhi, ylo, yhi, coords, c_diag, c_off,
                       nx, ny):
    """Oracle for kernels.stencil7.stencil_planes (padded assembly form)."""
    import numpy as np
    P = jnp.concatenate([xlo, T, xhi], axis=0)
    col = jnp.concatenate(
        [jnp.zeros((1, 1, T.shape[2]), T.dtype)] * 1, axis=0)
    ylo_p = jnp.concatenate([jnp.zeros((1, 1, T.shape[2]), T.dtype),
                             ylo, jnp.zeros((1, 1, T.shape[2]), T.dtype)],
                            axis=0)
    yhi_p = jnp.concatenate([jnp.zeros((1, 1, T.shape[2]), T.dtype),
                             yhi, jnp.zeros((1, 1, T.shape[2]), T.dtype)],
                            axis=0)
    P = jnp.concatenate([ylo_p, P, yhi_p], axis=1)
    out = affine_stencil_ref(P, c_diag, c_off)
    bx, by, nz = T.shape
    cx, cy = int(coords[0, 0]), int(coords[0, 1])
    gx = cx * bx + np.arange(bx)[:, None, None]
    gy = cy * by + np.arange(by)[None, :, None]
    zi = np.arange(nz)[None, None, :]
    interior = ((gx > 0) & (gx < nx - 1) & (gy > 0) & (gy < ny - 1)
                & (zi > 0) & (zi < nz - 1))
    return jnp.where(jnp.asarray(interior), out, T)
