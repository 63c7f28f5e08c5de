"""Device time a step under Kimi delta attention's scopes (``kda.proj``,
``kda.conv``, ``kda.gate``, ``kda.rule``, ``kda.norm``) outside the
rule's kernels (the events named ``kda_*``, which ``kda_roofline``
reads): the projections, the convolution, the gates, the L2 norms and
the gated norm, forward and backward."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("kda.",), less="kda_")
