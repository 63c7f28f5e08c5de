"""The registry's hvd_negotiation_seconds, summed over the window, a step."""
from chipbench import readers

LAYER = "Eager adapter and cycle"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    rec = readers.histogram(ctx, 'hvd_negotiation_seconds')
    return None if rec is None else readers.per_step_ms(ctx, rec['sum'])
