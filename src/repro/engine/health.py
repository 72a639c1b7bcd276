"""Engine-level numerical health: explicit-path ``isfinite`` sentinels.

The implicit path classifies failures *inside* its guarded Krylov loops
(:mod:`repro.solver.health`); an explicit time loop has no residual to
watch, so the executor instead probes field-state finiteness at the
checkpoint-chunk granule when ``RunOptions(check_finite=N)`` arms it.  A
probe is one fused ``isfinite``/``all`` reduction per field — amortized
over N steps it stays under the documented 2% overhead gate — and a trip
aborts the run with :class:`NumericalFault` carrying the offending step
index plus the last state that passed a probe (``last_good``).

The failure taxonomy, recovery policy and fault type are shared with the
solver layer; this module re-exports them so engine/service code has one
import surface.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.stats import stats
from repro.solver.health import (  # noqa: F401  (re-exports)
    NumericalFault,
    RecoveryPolicy,
    RecoveryTrace,
)


def probe_ok(env, pad: int = 0) -> jnp.ndarray:
    """Traceable scalar predicate: every buffer in ``env`` is all-finite.

    ``pad`` is the margin of halo-resident buffers
    (:class:`~repro.engine.layout.HaloLayout`): only their interiors are
    read, since margins are transient and may hold anything between
    launches.
    """
    ok = jnp.bool_(True)
    for v in env.values():
        if pad:
            v = v[..., pad:-pad, pad:-pad, :]
        ok = ok & jnp.all(jnp.isfinite(v))
    return ok


# compiled once per env tree/shape set: the eager per-op dispatch of the
# reduction chain is what would blow the 2% probe budget, not the FLOPs
probe_ok_compiled = jax.jit(probe_ok, static_argnums=1)


def probe(env) -> bool:
    """Host-side sentinel: True when every field buffer is finite.

    Counts itself in ``stats.health_probes``.  Works on device arrays
    (including sharded globals) and host numpy alike.
    """
    stats.health_probes += 1
    return bool(jax.device_get(probe_ok_compiled(dict(env))))


def poisoned_fields(env) -> list:
    """Names of the env fields holding non-finite values (host-side)."""
    return [
        k
        for k, v in env.items()
        if not np.all(np.isfinite(np.asarray(jax.device_get(v))))
    ]
