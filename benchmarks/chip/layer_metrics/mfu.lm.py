"""Model FLOP/s utilization: the PaLM count of the configuration's shapes x the rate / the chip's published bf16 peak."""
from chipbench import readers

LAYER = "User's jitted step"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return readers.mfu(ctx)
