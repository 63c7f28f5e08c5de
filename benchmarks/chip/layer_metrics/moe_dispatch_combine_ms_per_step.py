"""Device time a step under ``moe.dispatch`` and ``moe.combine``: the
sort by expert, the gathers into expert order and back, the gate
weights; what surrounds the grouped products on the routed path."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("moe.dispatch", "moe.combine"))
