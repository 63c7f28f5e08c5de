"""Milliseconds a step packing into and unpacking from the fusion buffer (the program's hvd.pack and hvd.unpack spans); none where the backend packs nothing."""
from chipbench import program_spans

LAYER = "Fusion"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    return program_spans.span_ms_per_step(ctx, 'hvd.pack', 'hvd.unpack')
