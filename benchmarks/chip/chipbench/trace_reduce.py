"""From a profiler trace to numbers: the reduction every PR shares.

A trace here is a plain structure, ``{"planes": [{"name": str, "lines":
[{"name": str, "events": [[name, start_ns, duration_ns], ...]}]}]}``,
read from the profiler's ``.xplane.pb`` by :func:`load_xplane` or from
a recorded JSON file by the tests. Times are nanoseconds on the
profiler's one clock, device and host alike.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
SHORT_GAP_NS = 5_000
NO_SPAN = "_no_span_"
SHORT_GAPS = "gaps_under_5_us"

Event = Tuple[str, float, float]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


TARGET = re.compile(r'custom_call_target="([^"]+)"')
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute")
# the opcode stands before its operands' bracket; an operand named
# ``%all-reduce.5`` is followed by a comma or the closing bracket
COLLECTIVE_OP = re.compile(
    r"(?<![%\w.\-])((?:" + "|".join(COLLECTIVES) + r")(?:-start|-done)?)\(")


def short_name(text: str) -> str:
    """``fusion.2031`` from the HLO instruction the profiler names a
    device event by (``%fusion.2031 = f32[...] fusion(...)``); a custom
    call keeps its target, which is how a Pallas kernel is told from
    the compiler's own calls: ``custom-call.7[tpu_custom_call]``. A
    collective whose name does not say what it is keeps its opcode:
    JAX's ``psum`` of one array comes out as ``psum.797[all-reduce]``,
    a combined one as ``all-reduce.58``."""
    if not text.startswith("%"):
        return text
    name = text[1:].split(" ", 1)[0]
    target = TARGET.search(text)
    if target:
        return f"{name}[{target.group(1)}]"
    op = COLLECTIVE_OP.search(text)
    if op and not name.startswith(op.group(1)):
        return f"{name}[{op.group(1)}]"
    return name


def is_collective(name: str) -> bool:
    """An event of :func:`short_name`'s that is a collective op, by its
    own name or by the opcode kept behind it."""
    return name.startswith(COLLECTIVES) or (
        name.endswith("]") and name.rsplit("[", 1)[-1].startswith(COLLECTIVES))


def load_xplane(path: str, keep_host=lambda name: name.startswith(
        SPAN_PREFIX)) -> dict:
    """The trace at ``path`` with every device event and, of the host's
    events, those ``keep_host`` accepts (the rest are thousands of
    Python frames nothing here reads)."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            events = [[short_name(e.name) if device else e.name,
                       float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or keep_host(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_op_events(trace: dict) -> Dict[str, List[Event]]:
    """``{device plane: its XLA-op events}``; a device plane without
    the ops line is an error, since busy time would read as zero."""
    out = {}
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PLANE):
            continue
        lines = [l for l in plane["lines"] if l["name"] == OPS_LINE]
        if not lines:
            raise ValueError(
                f"device plane {plane['name']} has no {OPS_LINE!r} line: "
                f"{[l['name'] for l in plane['lines']]}")
        out[plane["name"]] = [tuple(e) for e in lines[0]["events"]]
    return out


def host_spans(trace: dict, prefix: str = SPAN_PREFIX) -> List[Event]:
    spans = []
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE):
            continue
        for line in plane["lines"]:
            spans += [tuple(e) for e in line["events"]
                      if e[0].startswith(prefix)]
    return sorted(spans, key=lambda e: e[1])


def window_of(trace: dict) -> Tuple[float, float]:
    """(start, end) of the one ``bench.window`` span."""
    spans = [s for s in host_spans(trace) if s[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(spans)}")
    return spans[0][1], spans[0][1] + spans[0][2]


def clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_intervals(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, merged and sorted."""
    merged: List[List[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(a, b) for a, b in merged]


def busy_ns(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in busy_intervals(events))


def idle_gaps(events: Sequence[Event], t0: float,
              t1: float) -> List[Tuple[float, float]]:
    """The intervals of [t0, t1] in which no event runs."""
    gaps, cursor = [], t0
    for a, b in busy_intervals(clip(events, t0, t1)):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if t1 > cursor:
        gaps.append((cursor, t1))
    return gaps


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Nanoseconds by op name with nested ops taken out of their
    parents: a ``while`` holds its body's ops on the same line, and its
    own time is what they leave."""
    totals: Dict[str, float] = {}
    stack: List[List] = []          # [name, end, self]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + own

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


def time_of(events: Sequence[Event], match) -> float:
    """Busy nanoseconds of the events whose name ``match`` accepts."""
    return busy_ns([e for e in events if match(e[0])])


def attribute_gaps(gaps: Sequence[Tuple[float, float]],
                   spans: Sequence[Event]) -> Dict[str, float]:
    """Nanoseconds of idle time by the innermost ``bench.*`` span open
    on the host at the gap's middle; the window span itself counts as
    no span. Gaps under 5 us are summed apart: launch gaps, not waits."""
    spans = [s for s in spans if s[0] != WINDOW_SPAN]
    starts = [s[1] for s in spans]
    longest = max((s[2] for s in spans), default=0.0)
    out: Dict[str, float] = {}
    for a, b in gaps:
        if b - a < SHORT_GAP_NS:
            out[SHORT_GAPS] = out.get(SHORT_GAPS, 0.0) + (b - a)
            continue
        mid = (a + b) / 2
        name, best = NO_SPAN, None
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            s_name, s_start, s_dur = spans[i]
            if s_start + s_dur >= mid and (best is None or s_dur < best):
                name, best = s_name, s_dur
            if mid - s_start > longest:
                break
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce_trace(trace: dict) -> dict:
    """What the per-layer readers share: per device the clipped op
    events, and over the devices the mean busy seconds, the window, the
    ten ops with most self time and the ten largest gap owners."""
    t0, t1 = window_of(trace)
    per_device = {name: clip(ev, t0, t1)
                  for name, ev in device_op_events(trace).items()}
    if not per_device:
        raise ValueError("the trace has no device plane")
    spans = host_spans(trace)
    busy = [busy_ns(ev) for ev in per_device.values()]
    ops: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for ev in per_device.values():
        for k, v in self_times(ev).items():
            ops[k] = ops.get(k, 0.0) + v
        for k, v in attribute_gaps(idle_gaps(ev, t0, t1), spans).items():
            gaps[k] = gaps.get(k, 0.0) + v
    n = len(per_device)

    def top(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"window_s": (t1 - t0) / 1e9,
            "busy_s": sum(busy) / n / 1e9,
            "devices": n,
            "events": per_device,
            "spans": clip(spans, t0, t1),
            "device_ops": top(ops), "idle_gaps": top(gaps)}
