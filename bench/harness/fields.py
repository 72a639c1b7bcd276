"""Seeded input fields, made on the device in one jitted call."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def interior(shape):
    """True off the Moat: every cell but the x/y faces and the z end planes."""
    nx, ny, nz = shape
    ix = jnp.arange(nx)[:, None, None]
    iy = jnp.arange(ny)[None, :, None]
    iz = jnp.arange(nz)[None, None, :]
    return ((ix > 0) & (ix < nx - 1) & (iy > 0) & (iy < ny - 1)
            & (iz > 0) & (iz < nz - 1))


@partial(jax.jit, static_argnames=("shape", "count", "plate", "dtype"))
def _plates(key, *, shape, count, plate, dtype):
    cold, hot, init, amp = plate
    base = jnp.full(shape, init, jnp.float32)
    base = base.at[1:-1, 1:-1, 0].set(cold).at[1:-1, 1:-1, -1].set(hot)
    mask = interior(shape)
    out = []
    for k in jax.random.split(key, count):
        noise = jax.random.uniform(k, shape, jnp.float32, -amp, amp)
        field = jnp.where(mask, base + noise, base).astype(dtype)
        out.append(field)
    return tuple(out)


def plates(seed: int, shape, count: int, plate: dict, dtype="float32"):
    """``count`` hot plates (the paper's Fig. 3 set-up): ``init`` everywhere,
    ``cold`` and ``hot`` on the interior of the z end planes, and the
    interior perturbed by a seeded uniform draw of amplitude
    ``perturbation``.  Same seed, same fields."""
    spec = (float(plate["cold"]), float(plate["hot"]), float(plate["init"]),
            float(plate["perturbation"]))
    return _plates(jax.random.key(seed), shape=tuple(shape), count=count,
                   plate=spec, dtype=jnp.dtype(dtype).name)
