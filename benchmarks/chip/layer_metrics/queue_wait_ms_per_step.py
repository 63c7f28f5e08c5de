"""Milliseconds a step that batches sat in the queue, earliest enqueue to the loop's pop (the program's hvd.queue_wait intervals)."""
from chipbench import program_spans

LAYER = "Eager adapter and cycle"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    return program_spans.span_ms_per_step(ctx, 'hvd.queue_wait')
