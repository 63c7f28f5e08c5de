"""1 - the union of the device's op intervals / the traced window, in percent."""
from chipbench import readers

LAYER = "Device"
UNIT = "%"
MOVES = "images_per_s_chip.eager"


def read(ctx):
    return readers.idle_share(ctx)
