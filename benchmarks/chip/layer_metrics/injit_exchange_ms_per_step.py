"""Device time a step under ``exchange``: the synchronous all-reduces,
and the ``async-collective-start`` and ``-done`` fusions of those the
compiler laid under the backward (the waits for what compute did not
cover), which ``collective_time_share`` does not count. What the
transfers cost the ops they run under is in no event of these."""
from chipbench import scope_readers

LAYER = "User's jitted step"
UNIT = "ms"
MOVES = "tokens_per_s_chip"


def read(ctx):
    return scope_readers.scope_ms_per_step(ctx, ("exchange",))
