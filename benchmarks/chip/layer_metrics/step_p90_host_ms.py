"""The 90th percentile of a step's host milliseconds: the eager cells' tail, host-paced."""
from chipbench import readers

LAYER = "Eager adapter and cycle"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    return readers.percentile(ctx['step_ms'], 0.9)
