"""Seconds of set-up spent in lower().compile() of the window's programs: a load from the compile cache after a checkout's first run."""
from chipbench import readers

LAYER = "Launcher and start-up"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    return ctx['phases']['compile_or_load']
