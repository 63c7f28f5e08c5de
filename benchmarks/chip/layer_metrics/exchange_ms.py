"""Host milliseconds a step spends in hvd.allreduce_gradients (the bench.exchange span)."""
from chipbench import readers

LAYER = "Eager adapter and cycle"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    return readers.span_ms_per_step(ctx, 'bench.exchange')
