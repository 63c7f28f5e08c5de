"""The grouped matrix products' device time over the device's busy
time: the events of the ops line that the family names as the expert
layers' grouped products (``families/glm_moe_lm.py`` KERNEL_NAMES:
what the TPU compiler lowers ``jax.lax.ragged_dot`` to, its metadata
kernel included)."""
from chipbench import moe_readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return moe_readers.time_share(ctx, "grouped")
