"""Device time a step under the program's scope ``lm_head_loss`` (the
chunked head loss, forward and backward in one pass since PR 34; the
multi-token module's second pass through it included): the self time
of the events whose instruction the program's own table puts there
(``chipbench/scope_readers.py``)."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("lm_head_loss",))
