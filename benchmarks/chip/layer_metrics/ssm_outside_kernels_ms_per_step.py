"""Device time a step under Mamba's scopes (``ssm.proj``, ``ssm.conv``,
``ssm.scan``, ``ssm.gate``) outside the scan's kernels (the events
named ``ssm_scan_*``, which ``ssm_scan_roofline`` reads)."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("ssm.",), less="ssm_scan_")
