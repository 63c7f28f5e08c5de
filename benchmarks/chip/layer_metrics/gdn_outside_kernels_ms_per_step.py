"""Device time a step under the Gated DeltaNet's scopes (``gdn.proj``,
``gdn.conv``, ``gdn.rule``, ``gdn.gate``) outside the rule's kernels
(the events named ``gdn_*``, which ``gdn_roofline`` reads): the
projections, the gates, and the Pallas kernels that are not the rule's,
``qkv_prologue_*`` under ``gdn.conv`` and ``delta_epilogue_*`` under
``gdn.gate``, whose names have no ``gdn_`` in front, as
``kda_outside_kernels_ms_per_step`` counts them under ``kda.*``."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("gdn.",), less="gdn_")
