"""Milliseconds a step firing the callbacks that mark a batch's handles done (the program's hvd.complete spans)."""
from chipbench import program_spans

LAYER = "Eager adapter and cycle"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    return program_spans.span_ms_per_step(ctx, 'hvd.complete')
