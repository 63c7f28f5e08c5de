"""The device time of the events named ``flash_fwd``, ``flash_bwd_dq``
and ``flash_bwd_dkv`` (the family's ``KERNEL_NAMES``) over the device's
busy time, in a model whose attention is windowed in some layers and
full in others, over fewer key-value heads than query heads."""
from chipbench import moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return moe_readers.time_share(ctx, "flash")
