"""What the readers of the program's own spans share.

The program opens one vocabulary of spans (``hvd.init`` ...
``hvd.complete``: ``horovod_tpu/common/trace.py``, docs/tracing.md).
Their durations reach a reader through the registry
(``hvd_span_seconds{span=...}``, growth over the window, in
``ctx["registry"]``); their intervals through the ring the program
keeps in memory (``trace.recent_spans()``), which outlives
``hvd.shutdown()``. The ring stamps with ``time.time_ns()``; the
harness's trace holds the profiler's events on the same clock minus
the session's start, a constant that :func:`ring_offset_ns` finds from
the spans both have. A program without such spans (this benchmark laid
over an older commit) gives every reader here ``None``.
"""

from __future__ import annotations

import bisect

from chipbench import readers, trace_reduce

METRIC = "hvd_span_seconds"
EXCHANGE = ("bench.exchange", "hvd.allreduce_gradients")
MAIN_THREAD = "MainThread"
ALIGN_LIMIT_NS = 100_000        # the two clocks agree to this, or no value
NO_BACKGROUND_SPAN = "(background: no span open)"


def span_seconds(ctx, name):
    """Seconds the window spent under the program's span ``name``, or
    ``None`` where the registry has no such span."""
    rec = readers.histogram(ctx, f'{METRIC}{{span="{name}"}}')
    return None if rec is None else rec["sum"]


def span_ms_per_step(ctx, *names):
    """Milliseconds a step under the spans ``names`` together; ``None``
    where the registry has none of them."""
    found = [s for s in (span_seconds(ctx, n) for n in names)
             if s is not None]
    return readers.per_step_ms(ctx, sum(found)) if found else None


def ring():
    """The program's closed spans, oldest first, or ``None`` where it
    keeps no ring or the ring has dropped any."""
    from horovod_tpu.common import trace
    if not hasattr(trace, "recent_spans") or trace.spans_dropped():
        return None
    return trace.recent_spans()


def first_span(name):
    """The process's first closed span ``name``, or ``None``."""
    return next((s for s in ring() or () if s.name == name), None)


def window(ctx):
    """(start, end) of ``bench.window`` on the trace's clock."""
    start, dur = next((s, d) for n, s, d in ctx["trace"]["spans"]
                      if n == trace_reduce.WINDOW_SPAN)
    return start, start + dur


def ring_offset_ns(ctx, spans):
    """What to take from a ring span's ``time.time_ns()`` to be on the
    trace's clock. The harness's ``bench.exchange`` spans and the
    program's ``hvd.allreduce_gradients`` are the same calls, one a few
    microseconds inside the other: the run of ring spans whose starts
    differ from the trace's by one constant gives it (the smallest
    difference: the inner span starts last, and a step the host was
    held up in starts later still). ``None`` where in no run half the
    differences lie within ``ALIGN_LIMIT_NS`` of the smallest."""
    outer = sorted(s for n, s, _ in ctx["trace"]["spans"]
                   if n == EXCHANGE[0])
    inner = sorted(s.start_ns for s in spans if s.name == EXCHANGE[1])
    best = None
    for k in range(len(inner) - len(outer) + 1):
        gaps = sorted(i - o for i, o in zip(inner[k:], outer))
        if gaps and (best is None
                     or gaps[len(gaps) // 2] - gaps[0] < best[0]):
            best = (gaps[len(gaps) // 2] - gaps[0], gaps[0])
    if best is None or best[0] > ALIGN_LIMIT_NS:
        return None
    return best[1]


def aligned_ring(ctx):
    """The ring's spans as the trace's events ``(name, start, dur)`` on
    the trace's clock, clipped to the window, by thread: ``{thread:
    [events]}``; ``None`` without a trace, a ring or an alignment."""
    spans = ring()
    if ctx["trace"] is None or not spans:
        return None
    offset = ring_offset_ns(ctx, spans)
    if offset is None:
        ctx["notes"].append(
            f"program spans: the ring's {EXCHANGE[1]} spans do not line "
            f"up with the trace's {EXCHANGE[0]} spans to within "
            f"{ALIGN_LIMIT_NS} ns; the clocks are not aligned, so no "
            f"idle time is given to them")
        return None
    t0, t1 = window(ctx)
    by_thread: dict = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(
            (s.name, float(s.start_ns - offset),
             float(s.end_ns - s.start_ns)))
    return {thread: trace_reduce.clip(events, t0, t1)
            for thread, events in by_thread.items()}


def idle_under(ctx, name):
    """The device's idle nanoseconds inside the window while the main
    thread's span ``name`` is open, by what the program was doing:
    ``{(main thread's innermost span, other threads' innermost span):
    ns}``, a device's mean. A gap goes to the spans open at its middle
    and gaps under 5 us are left out (launch gaps), as in
    ``trace_reduce.attribute_gaps``. ``None`` without a trace, a ring
    or an alignment."""
    threads = aligned_ring(ctx)
    if threads is None:
        return None
    main = innermost_at(threads.get(MAIN_THREAD, []))
    named = innermost_at([e for e in threads.get(MAIN_THREAD, [])
                          if e[0] == name])
    other = innermost_at([e for thread, events in threads.items()
                          if thread != MAIN_THREAD for e in events])
    t0, t1 = window(ctx)
    devices = ctx["trace"]["events"]
    out: dict = {}
    for events in devices.values():
        for a, b in trace_reduce.idle_gaps(events, t0, t1):
            mid = (a + b) / 2
            if b - a < trace_reduce.SHORT_GAP_NS or named(mid) is None:
                continue
            key = (main(mid), other(mid) or NO_BACKGROUND_SPAN)
            out[key] = out.get(key, 0.0) + (b - a) / len(devices)
    return out


def innermost_at(spans):
    """``at -> name`` of the shortest of ``spans`` open at time ``at``,
    or ``None``."""
    spans = sorted(spans, key=lambda e: e[1])
    starts = [e[1] for e in spans]
    longest = max((e[2] for e in spans), default=0.0)

    def find(at):
        name, best = None, None
        for i in range(bisect.bisect_right(starts, at) - 1, -1, -1):
            s_name, s_start, s_dur = spans[i]
            if at - s_start > longest:
                break
            if s_start + s_dur >= at and (best is None or s_dur < best):
                name, best = s_name, s_dur
        return name

    return find
