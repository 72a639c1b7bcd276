"""Percent of device busy time in the solves outside the operator kernel:
Krylov's dot products, axpys and the stopping test."""
from bench.harness import hlo, trace


def read(ctx):
    if ctx.trace is None:
        return None
    return trace.share_outside(ctx.trace, hlo.is_stencil_kernel)
