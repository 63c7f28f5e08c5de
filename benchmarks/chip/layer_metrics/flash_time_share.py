"""The flash kernels' device time over the device's busy time."""
from chipbench import readers

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    spent = readers.kernel_seconds(ctx, readers.is_pallas_call)
    if not spent:
        return None
    return 100.0 * spent / ctx["trace"]["busy_s"]
