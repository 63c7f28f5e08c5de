"""The device time of the events named ``flash_fwd``, ``flash_bwd_dq``
and ``flash_bwd_dkv`` (the family's ``KERNEL_NAMES``) over the device's
busy time, in a model whose latent attention has a score head wider
than its value head."""
from chipbench import moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    if "nope" not in ctx["sz"] or "v_dim" not in ctx["sz"]:
        return None
    return moe_readers.time_share(ctx, "flash")
