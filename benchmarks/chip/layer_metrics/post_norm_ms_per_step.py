"""Device time a step under ``post_norm``: the two RMSNorms a block
that sit on the outputs of its mixer and of its feed-forward, with the
residual adds they feed, forward, recomputed and backward."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("post_norm",))
