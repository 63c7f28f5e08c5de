"""Samples completed in the window per second and per chip: steps x
samples a step / window / chips, over all the work and all the time."""


def read(ctx):
    return ctx["rate"]
