"""Milliseconds a step inside the backend's allreduce (hvd_collective_seconds of allreduce)."""
from chipbench import readers

LAYER = "Data plane"
UNIT = "ms"
MOVES = "images_per_s_chip"


def read(ctx):
    rec = readers.histogram(ctx, 'hvd_collective_seconds{op="allreduce"}')
    return None if rec is None else readers.per_step_ms(ctx, rec['sum'])
