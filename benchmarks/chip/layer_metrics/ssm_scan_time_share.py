"""The device time of the events named ``ssm_scan_fwd`` and ``ssm_scan_bwd``
(the family's ``KERNEL_NAMES``) over the device's busy time."""
from chipbench import moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return moe_readers.time_share(ctx, "scan")
