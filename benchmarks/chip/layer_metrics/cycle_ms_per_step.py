"""Background-thread milliseconds a step in passes of the loop that popped work (the program's hvd.cycle spans: hold, negotiate, execute, complete)."""
from chipbench import program_spans

LAYER = "Eager adapter and cycle"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    return program_spans.span_ms_per_step(ctx, 'hvd.cycle')
