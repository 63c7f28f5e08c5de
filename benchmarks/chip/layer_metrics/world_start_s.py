"""Seconds of set-up spent starting the world: the launcher and its ranks where there are any, and hvd.init()."""
from chipbench import readers

LAYER = "Launcher and start-up"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    return ctx['phases']['world_start']
