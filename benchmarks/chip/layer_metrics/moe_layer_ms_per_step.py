"""Device time a step under ``moe``, ``moe.route``, ``moe.dispatch``,
``moe.experts`` and ``moe.combine``: the whole expert layer of a model
without a shared expert, the router, the sort, the gathers, the grouped
products and the way back to token order, forward and backward."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(
        ctx, ("moe", "moe.route", "moe.dispatch", "moe.experts",
              "moe.combine"))
