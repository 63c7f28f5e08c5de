"""1 - the union of the device's op intervals / the traced window, in percent."""
from chipbench import readers

LAYER = "Device"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return readers.idle_share(ctx)
