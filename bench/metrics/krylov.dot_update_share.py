"""Percent of device busy time in the solves in Krylov's own vector
passes: operations under the program's ``wfa.krylov.dot`` (reductions)
or ``wfa.krylov.update`` (the x, r and p updates) scopes.  The notes give
the whole split by scope."""
from bench.harness import scopes


def read(ctx):
    return scopes.scope_share(ctx, scopes.KRYLOV, "scopes")
