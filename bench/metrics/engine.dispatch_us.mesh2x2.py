"""Host us per chunk of the window inside the program's
``wfa.engine.dispatch`` spans (one per call of the engine's ``shard_map``
runner): the engine's own dispatch to the mesh, on the host clock
alone."""
from bench.harness import scopes


def read(ctx):
    return scopes.dispatch_us(ctx, "wfa.engine.dispatch", "dispatch")
