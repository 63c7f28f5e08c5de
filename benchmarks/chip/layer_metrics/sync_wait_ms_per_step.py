"""Main-thread milliseconds a step inside handle waits (the program's hvd.synchronize spans); holds the wait for the backward."""
from chipbench import program_spans

LAYER = "Eager adapter and cycle"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    return program_spans.span_ms_per_step(ctx, 'hvd.synchronize')
