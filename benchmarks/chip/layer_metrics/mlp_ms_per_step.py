"""Device time a step under the dense feed-forwards (scope ``mlp``) and
the shared experts (``moe.shared``)."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("mlp", "moe.shared"))
