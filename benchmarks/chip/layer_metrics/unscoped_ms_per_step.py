"""Device time a step of the events the by-scope table cannot place:
those on whose path no scope of the program's vocabulary lies (the
converts at the step's boundary) and around which none is found, with
the events the program's table does not know."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("",))
