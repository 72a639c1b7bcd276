"""The window's length over the solves it completed to the configuration's
tolerance, in ms."""


def read(ctx):
    w = ctx.window
    return w.seconds * 1e3 / len(w.done) if w.done else None
