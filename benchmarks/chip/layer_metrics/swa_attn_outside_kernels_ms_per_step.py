"""Device time a step under the scope ``swa_attn`` (attention inside a
sliding window, with its rotary) outside the flash kernels (the events
named ``flash_*``, which ``swa_flash_roofline`` reads): projections,
the rotary, the ``[B,S,H,D]`` transposes, the residual add."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("swa_attn",), less="flash_")
