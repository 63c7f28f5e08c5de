"""The compiler's memory_analysis of the window's largest executable: arguments + outputs - aliased + temporaries + code."""
from chipbench import readers

LAYER = "Device"
UNIT = "GB"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return readers.hbm_need_gb(ctx)
