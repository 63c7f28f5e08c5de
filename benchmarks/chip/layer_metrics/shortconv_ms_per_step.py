"""Device time a step under ``shortconv.proj`` and ``shortconv.conv``:
the gated short convolution whole, its two projections (d -> 3d, d ->
d), both gates and the three taps, forward and backward."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(
        ctx, ("shortconv.proj", "shortconv.conv"))
