"""Seconds of set-up inside the process's first hvd.broadcast_parameters (the program's span of that name, from its ring)."""
from chipbench import program_spans

LAYER = "Launcher and start-up"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    span = program_spans.first_span('hvd.broadcast_parameters')
    return None if span is None else (span.end_ns - span.start_ns) / 1e9
