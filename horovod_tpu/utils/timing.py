"""Steady-state step timing for benchmarks.

One shared implementation of the discipline bench.py and the examples
need:

- warm up past compilation and the first dispatches;
- time in chunks, each ended by ``sync`` on the last step's handle
  (``jax.block_until_ready`` or a value fetch: chip_smoke.py checks on
  the chip that the two take the same time);
- report the median chunk, as plain noise hygiene: a one-chip machine
  shares its host's cores.
"""

from __future__ import annotations

import time
from typing import Callable


def steady_state_sec_per_step(step: Callable[[], object],
                              sync: Callable[[object], None],
                              warmup_steps: int = 10,
                              chunks: int = 4,
                              chunk_steps: int = 5) -> float:
    """Median seconds per ``step()`` call at steady state.

    ``step`` runs one (async-dispatched) training step and returns a
    handle; ``sync`` forces completion of that handle (e.g.
    ``lambda r: float(r[-1])`` fetching the loss). Runs
    ``warmup_steps`` (0 allowed, for cold-start measurements) then
    ``chunks`` timed chunks of ``chunk_steps`` (each clamped to >= 1).
    """
    import numpy as np

    r = None
    for _ in range(max(0, warmup_steps)):
        r = step()
    if r is not None:
        sync(r)
    dts = []
    for _ in range(max(1, chunks)):
        t0 = time.perf_counter()
        for _ in range(max(1, chunk_steps)):
            r = step()
        sync(r)
        dts.append((time.perf_counter() - t0) / max(1, chunk_steps))
    return float(np.median(dts))
