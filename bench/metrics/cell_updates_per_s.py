"""Global cells times steps completed in the window, over the window's
seconds, in Gcell/s."""


def read(ctx):
    w = ctx.window
    return sum(u.work for u in w.done) / w.seconds / 1e9
