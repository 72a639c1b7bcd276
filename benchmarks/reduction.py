"""Paper Eq. 17 + §3.2.2 reduction analysis: dot products and fused duals.

Measures the host cost of the CG reductions (separate vs fused dual-dot,
the form ``repro.kernels.ops.dual_dot`` takes) and evaluates the paper's
latency models against the distributed-computing numbers it cites
(MVAPICH 15–35 µs at 1024 nodes, GPU >100 µs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.core.perfmodel import (TPU_V5E_ICI_LAT, wse_dot_time)


def run() -> None:
    rng = np.random.default_rng(0)
    shape = (64, 128, 64)
    a, b, c, d = [jnp.asarray(rng.normal(size=shape).astype(np.float32))
                  for _ in range(4)]

    two = jax.jit(lambda a, b, c, d: (jnp.sum(a * b), jnp.sum(c * d)))
    us2 = time_fn(two, a, b, c, d)
    emit("dot_two_separate", us2, f"elems={a.size}")

    fused = jax.jit(lambda a, b, c, d: jnp.stack(
        [jnp.sum(a * b), jnp.sum(c * d)]))
    usf = time_fn(fused, a, b, c, d)
    emit("dot_fused_dual", usf, f"speedup_vs_separate={us2 / usf:.2f}")

    # Eq. 17: the paper's 3.25 µs full-fabric dot vs distributed baselines
    t = wse_dot_time(1000, 750, 950) * 1e6
    emit("wse_dot_model", t,
         "mvapich_1024node_us=15-35;gpu_allreduce_us>100;paper_us=3.25")

    # TPU analogue: psum latency is hop-latency × mesh diameter
    for mesh_xy in [(16, 16), (32, 16)]:
        hops = 2 * (mesh_xy[0] + mesh_xy[1])
        emit(f"tpu_psum_latency_model_{mesh_xy[0]}x{mesh_xy[1]}",
             hops * TPU_V5E_ICI_LAT * 1e6,
             f"diameter_hops={hops};per_hop_us={TPU_V5E_ICI_LAT * 1e6:.1f}")


if __name__ == "__main__":
    run()
