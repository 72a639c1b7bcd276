"""Percent of device busy time in the window in the conversions around
the kernel on a mesh: operations under the program's ``wfa.halo.pad``
(the concatenates that repack each brick with its halo every launch),
``wfa.engine.wrap_pad``, ``wfa.engine.margin_refresh`` or
``wfa.engine.layout`` scopes, over every chip.  The exchange itself is
``halo.exposed_collective_share``'s.  The notes give the whole split by
scope, the mean over the chips."""
from bench.harness import parsed, scopes


def read(ctx):
    if ctx.trace is None:
        return None
    group = scopes.MARGIN + ("wfa.halo.pad",)
    return parsed.share(ctx.trace, scopes.scope_map(), group, ctx.notes, "scopes")
