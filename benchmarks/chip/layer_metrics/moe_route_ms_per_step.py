"""Device time a step under ``moe.route``: the float32 router at full
precision, the top k, the gate weights."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("moe.route",))
