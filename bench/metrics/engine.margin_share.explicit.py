"""Percent of device busy time in the window in the engine's conversions
around the kernel: operations under the program's ``wfa.engine.wrap_pad``
(the per-launch wrap pad), ``wfa.engine.margin_refresh`` (the resident
layout's margin refresh) or ``wfa.engine.layout`` (its enter/exit)
scopes.  The notes give the whole split by scope."""
from bench.harness import scopes


def read(ctx):
    return scopes.scope_share(ctx, scopes.MARGIN, "scopes")
