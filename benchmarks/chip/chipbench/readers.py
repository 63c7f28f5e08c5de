"""What several metric files share: each file under ``end_to_end/`` and
``layer_metrics/`` is one metric, and metrics of one kind in different
cells differ only by name."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q`` quantile by linear interpolation between order
    statistics."""
    ordered = sorted(values)
    pos = q * (len(ordered) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def mfu(ctx):
    """Model FLOPs the rate implies over the chip's published peak. A
    rehearsal has no chip and so no peak, and its tiny sizes may be
    none the family keeps a FLOP count for: nothing to read. On the
    chip a size without a count stops the run, in the family's words."""
    if ctx["peak"] is None:
        return None
    flops = ctx["family"].flops_per_sample(ctx["sz"])
    return 100.0 * ctx["rate"] * flops / ctx["peak"].bf16_flops


def idle_share(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def hbm_need_gb(ctx):
    return ctx["need_bytes"] / 1e9


def histogram(ctx, name):
    """The window's growth of histogram ``name`` in the program's
    registry, or ``None`` where the registry was off or has none."""
    rec = ctx["registry"].get(name)
    return rec if isinstance(rec, dict) and rec["count"] else None


def per_step_ms(ctx, seconds):
    return 1e3 * seconds / ctx["steps"]


def span_ms_per_step(ctx, name):
    """Host milliseconds a step spends under the ``bench.*`` span
    ``name``, from the profiler's trace."""
    if ctx["trace"] is None:
        return None
    total = sum(d for n, _, d in ctx["trace"]["spans"] if n == name)
    return per_step_ms(ctx, total / 1e9) if total else None


def is_pallas_call(name: str) -> bool:
    """A Pallas kernel on the ops line: a custom call whose target is
    ``tpu_custom_call`` (``trace_reduce.short_name`` keeps the target).
    In the transformer's step these are the flash kernels and nothing
    else: three a layer."""
    return name.endswith("[tpu_custom_call]")


def kernel_seconds(ctx, match):
    """Device seconds of the events ``match`` accepts, a device's mean."""
    from chipbench import trace_reduce
    if ctx["trace"] is None:
        return None
    per_device = [trace_reduce.time_of(events, match)
                  for events in ctx["trace"]["events"].values()]
    return sum(per_device) / len(per_device) / 1e9
