"""Mean fill of a fused batch against the fusion threshold (hvd_fusion_fill_ratio)."""
from chipbench import readers

LAYER = "Fusion"
UNIT = "%"
MOVES = "images_per_s_chip"


def read(ctx):
    rec = readers.histogram(ctx, 'hvd_fusion_fill_ratio')
    return None if rec is None else 100.0 * rec['sum'] / rec['count']
