"""Percent of device busy time in the window outside the fused kernel: the
engine's wrap pads, copies, slices, layout enter/exit and, on a mesh, the
halo exchange."""
from bench.harness import hlo, trace


def read(ctx):
    if ctx.trace is None:
        return None
    return trace.share_outside(ctx.trace, hlo.is_stencil_kernel)
