"""The device time of the events named ``flash_fwd``, ``flash_bwd_dq``
and ``flash_bwd_dkv`` (the family's ``KERNEL_NAMES``) over the device's
busy time, in a model whose attention runs over fewer key-value heads
than query heads at one head size."""
from chipbench import moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return moe_readers.time_share(ctx, "flash")
