"""Host us per solve of the window inside the program's
``wfa.solver.dispatch`` spans (the copy of x0 and the solver program's
dispatch): the solver's own dispatch, on the host clock alone.  The notes
give the copy's mean length (``wfa.solver.copy_x0``)."""
from bench.harness import scopes


def read(ctx):
    return scopes.dispatch_us(ctx, "wfa.solver.dispatch", "dispatch")
