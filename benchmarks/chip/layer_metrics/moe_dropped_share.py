"""Assignments to a held expert that the layer did not compute, over
all assignments to held experts in the window
(``hvd_moe_dropped_total`` over ``hvd_moe_assignments_total{held="1"}``).
The layer drops none: this reads 0."""
from chipbench import moe_readers

LAYER = "User's jitted step"
UNIT = "%"
MOVES = "tokens_per_s_chip"


def read(ctx):
    held = ctx["registry"].get(moe_readers.HELD)
    if not moe_readers.on_the_chip(ctx) or not held:
        return None
    return 100.0 * ctx["registry"].get("hvd_moe_dropped_total", 0) / held
