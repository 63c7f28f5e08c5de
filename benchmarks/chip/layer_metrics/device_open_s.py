"""Seconds of set-up until this process (at more ranks: every rank) has its chip: jax.devices() and a first operation."""

LAYER = "Launcher and start-up"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    return ctx['phases']['device_open']
