"""Seconds from the command's start to the first step of the window,
less the phase in which the platform opens the chip (``device_open`` on
the run's phases line): that phase drifts by seconds from one process to
the next whatever the code does, and no bound would hold over it."""


def read(ctx):
    return ctx["setup_s"]
