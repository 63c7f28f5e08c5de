"""What the readers of an expert-layer family share: kernels matched by
the names the family gives them, and the program's load counters."""

from __future__ import annotations

from chipbench import readers

HELD = 'hvd_moe_assignments_total{held="1"}'
STEPS = "hvd_moe_steps_total"


def on_the_chip(ctx) -> bool:
    """A rehearsal prints counts and ``correct`` only: its readers have
    nothing to report, the program's counters included."""
    return ctx["peak"] is not None


def kernel_seconds(ctx, kind: str):
    """Device seconds of the events whose name starts with one of the
    family's ``KERNEL_NAMES[kind]``, a device's mean; ``None`` without
    a trace or for a family that names no such kernels."""
    names = getattr(ctx["family"], "KERNEL_NAMES", {}).get(kind)
    if not names:
        return None
    return readers.kernel_seconds(ctx, lambda name: name.startswith(names))


def time_share(ctx, kind: str):
    """Those events' device time over the device's busy time, in
    percent."""
    spent = kernel_seconds(ctx, kind)
    if not spent:
        return None
    return 100.0 * spent / ctx["trace"]["busy_s"]


def held_rows_per_step(ctx):
    """Assignments to held experts in a step, all expert layers summed,
    over the steps the program counted in the window."""
    steps = ctx["registry"].get(STEPS)
    if not on_the_chip(ctx) or not steps:
        return None
    return ctx["registry"].get(HELD, 0) / steps
