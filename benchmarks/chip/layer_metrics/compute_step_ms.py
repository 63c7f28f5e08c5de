"""Device busy milliseconds a step (backward + apply), from the trace."""
from chipbench import readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    if ctx['trace'] is None:
        return None
    return readers.per_step_ms(ctx, ctx['trace']['busy_s'])
