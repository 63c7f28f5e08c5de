"""The fullest held expert's load over the mean load, the worst expert
layer of the last step the program counted (gauge
``hvd_moe_expert_load_max_over_mean``): 1.0 is an even spread."""
from chipbench import moe_readers

LAYER = "User's jitted step"
UNIT = "ratio"
MOVES = "tokens_per_s_chip"


def read(ctx):
    if not moe_readers.on_the_chip(ctx):
        return None
    return ctx["registry"].get("hvd_moe_expert_load_max_over_mean")
