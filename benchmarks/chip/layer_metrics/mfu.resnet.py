"""Model FLOP/s utilization: 3 x 2 x 4.089 G FLOPs an image x the rate / the chip's published bf16 peak."""
from chipbench import readers

LAYER = "User's jitted step"
UNIT = "%"
MOVES = "images_per_s_chip"


def read(ctx):
    return readers.mfu(ctx)
