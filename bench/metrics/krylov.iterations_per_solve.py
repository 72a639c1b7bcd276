"""Mean Krylov iterations per solve in the window, as the solver reports
them."""


def read(ctx):
    its = [u.extra["iterations"] for u in ctx.window.units if "iterations" in u.extra]
    return sum(its) / len(its) if its else None
