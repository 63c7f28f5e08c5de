"""The three flash kernels' device time over the device's busy time,
matched by their names (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``): this step has other ``tpu_custom_call``s beside
them."""
from chipbench import moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return moe_readers.time_share(ctx, "flash")
