"""Percent of the traced window in which no operation ran on the device
(1 minus the union of busy intervals over the window), averaged over the
chips used."""
from bench.harness import trace


def read(ctx):
    if ctx.trace is None:
        return None
    return trace.idle_share(ctx.trace)
