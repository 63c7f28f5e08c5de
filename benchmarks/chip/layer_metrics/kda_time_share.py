"""The device time of the events named ``kda_fwd`` and ``kda_bwd`` (the
family's ``KERNEL_NAMES``: the per-channel delta rule's two kernels)
over the device's busy time."""
from chipbench import moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return moe_readers.time_share(ctx, "kda")
