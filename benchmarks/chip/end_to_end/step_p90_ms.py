"""The 90th percentile of a step's milliseconds, each timed group of
steps ended by ``block_until_ready`` and spanning 250 ms or more."""
from chipbench.readers import percentile


def read(ctx):
    return percentile(ctx["step_ms"], 0.9)
