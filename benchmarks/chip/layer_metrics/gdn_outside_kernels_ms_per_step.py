"""Device time a step under the Gated DeltaNet's scopes (``gdn.proj``,
``gdn.conv``, ``gdn.rule``, ``gdn.gate``) outside the rule's kernels
(the events named ``gdn_*``, which ``gdn_roofline`` reads)."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("gdn.",), less="gdn_")
