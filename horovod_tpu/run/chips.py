"""One TPU chip for each local rank.

A chip belongs to one process at a time: N ranks spawned on a host
with N chips would each open all of them, so the first takes them and
the rest fail or hang at backend start. libtpu confines a process to a
subset of the host's chips through its environment
(``TPU_VISIBLE_CHIPS`` plus one-chip process bounds), so the launcher
exports that per local rank. It never imports jax: a launcher that
initialised a backend would itself hold the chips its children need.

The setting is exported only where ranks would otherwise share chips:
not for worlds forced to the CPU (``JAX_PLATFORMS`` without ``tpu``,
which every multi-process test uses), not for a single local rank (one
process may drive every chip: the in-jit path), not on hosts without
chips, and not where the operator already pinned chips.
"""

from __future__ import annotations

import glob
import re
from typing import Dict, Mapping, Optional

# Operator-set pins the launcher leaves alone.
_PINS = ("TPU_VISIBLE_CHIPS", "TPU_VISIBLE_DEVICES")

# A chip's device node: ``/dev/accelN``, or one vfio group per chip
# (``/dev/vfio/N``) on v5e and later. Group 1 is the node's number.
CHIP_NODE = re.compile(r"/dev/(?:accel|vfio/)(\d+)")


class ChipShortage(RuntimeError):
    """More local ranks than the host has chips."""


def visible_chips() -> int:
    """TPU chips this host exposes, counted from their device nodes.
    The PCI bus is no guide: a VM handed one chip of four still lists
    four functions there."""
    nodes = glob.glob("/dev/accel*") + glob.glob("/dev/vfio/*")
    return sum(1 for n in nodes if CHIP_NODE.fullmatch(n))


def forced_off_tpu(env: Mapping[str, str]) -> bool:
    platforms = env.get("JAX_PLATFORMS", "").strip().lower()
    return bool(platforms) and "tpu" not in platforms.split(",")


def chip_env(local_rank: int, local_size: int, env: Mapping[str, str],
             n_chips: Optional[int] = None) -> Dict[str, str]:
    """The variables that confine local rank ``local_rank`` of
    ``local_size`` to its own chip, or ``{}`` where none are needed
    (see the module docstring). ``env`` is the rank's environment so
    far; ``n_chips`` defaults to the host's :func:`visible_chips`.
    Refuses with :class:`ChipShortage`, instead of letting the ranks
    hang, when there are fewer chips than ranks."""
    if (local_size <= 1 or forced_off_tpu(env)
            or any(env.get(k) for k in _PINS)):
        return {}
    if n_chips is None:
        n_chips = visible_chips()
    if n_chips == 0:
        return {}
    if local_size > n_chips:
        raise ChipShortage(
            f"{local_size} local ranks would contend for {n_chips} TPU "
            f"chip(s): a chip belongs to one process. Launch at most "
            f"{n_chips} rank(s) here, or set JAX_PLATFORMS=cpu for a "
            f"host-only world.")
    # Measured on the v5e 2x2 host with libtpu 0.0.34 (PR 21): the
    # chip index alone is not enough — three of four such processes
    # die on libtpu's multi-process lockfile — and with the one-chip
    # bounds four ranks each open their own /dev/vfio group. No
    # per-process port is needed: a one-chip process builds no slice.
    return {
        "TPU_VISIBLE_CHIPS": str(local_rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
