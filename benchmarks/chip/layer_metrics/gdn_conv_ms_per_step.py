"""Device time a step under ``gdn.conv``: the causal depthwise
convolution of four taps over 8,192 channels and its SiLU, forward and
backward; where the program runs them as one Pallas pass each way
(``qkv_prologue_fwd``, ``qkv_prologue_bwd``: the taps, SiLU, the L2
norms, the scale and the casts), those kernels and the add that joins
their ``dx`` with ``dz``."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("gdn.conv",))
