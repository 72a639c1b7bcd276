"""Percent of device busy time in the solves in the engine's conversions
around the operator kernel: operations under the program's
``wfa.engine.wrap_pad``, ``wfa.engine.margin_refresh`` or
``wfa.engine.layout`` scopes.  The notes give the whole split by scope."""
from bench.harness import scopes


def read(ctx):
    return scopes.scope_share(ctx, scopes.MARGIN, "scopes")
