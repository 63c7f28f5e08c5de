"""Device time a step under ``shortconv.conv`` alone: the two gates
(``B u`` and ``C c``) and the causal depthwise convolution of three
taps over 2,048 channels, forward and backward; what a fused kernel
would attack."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("shortconv.conv",))
