"""Set-up: process start to the start of the window (loading, making the
seeded fields, warming every shape the window uses, and any compile)."""


def read(ctx):
    return ctx.setup_s
