"""Main-thread milliseconds a step handing tensors to the runtime (the program's hvd.enqueue spans: inspect, handles, table)."""
from chipbench import program_spans

LAYER = "Eager adapter and cycle"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    return program_spans.span_ms_per_step(ctx, 'hvd.enqueue')
