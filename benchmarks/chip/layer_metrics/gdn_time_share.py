"""The device time of the events named ``gdn_fwd`` and ``gdn_bwd`` (the
family's ``KERNEL_NAMES``: the gated delta rule's two kernels) over the
device's busy time."""
from chipbench import moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return moe_readers.time_share(ctx, "gdn")
