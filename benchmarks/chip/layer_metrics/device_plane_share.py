"""Share of the window's payload bytes served by a backend that keeps them on the device (xla_mesh); the rest crossed the host."""
from chipbench import readers

LAYER = "Data plane"
UNIT = "%"
MOVES = "images_per_s_chip"


def read(ctx):
    moved = {k: v for k, v in ctx['registry'].items()
             if k.startswith('hvd_backend_bytes_total') and v}
    if not moved:
        return None
    on_device = sum(v for k, v in moved.items() if 'backend="xla' in k)
    return 100.0 * on_device / sum(moved.values())
