"""Fused batches executed a step: observations of hvd_fusion_fill_ratio / steps."""
from chipbench import readers

LAYER = "Fusion"
UNIT = "count"
MOVES = "images_per_s_chip"


def read(ctx):
    rec = readers.histogram(ctx, 'hvd_fusion_fill_ratio')
    return None if rec is None else rec['count'] / ctx['steps']
