"""Horovod Timeline: Chrome-tracing profile of every collective.

(reference: horovod/common/timeline.{h,cc} — per-tensor state machine
NEGOTIATING → TOP_LEVEL → ACTIVITY, timeline.h:76; rank-0-only file
written by a dedicated thread fed from a lock-free queue,
timeline.h:46-74; enabled by ``HOROVOD_TIMELINE`` with optional cycle
markers via ``HOROVOD_TIMELINE_MARK_CYCLES``, operations.cc:792-798.)

Event vocabulary matches the reference so existing timeline tooling and
the reference's test greps carry over (reference:
test/test_timeline.py:42-58 greps NEGOTIATE_ALLREDUCE / ALLREDUCE /
CYCLE_START): one trace "process" per tensor name, ``NEGOTIATE_<OP>``
spans with per-rank instant ticks, a top-level ``<OP>`` span, nested
activity spans (QUEUE / MEMCPY_IN_FUSION_BUFFER / COLLECTIVE /
MEMCPY_OUT_FUSION_BUFFER), and ``CYCLE_START`` instants.
"""

from __future__ import annotations

import json
import queue
import threading
import time
from typing import Dict, Optional

from horovod_tpu.common import lockdep
from horovod_tpu.common import threadcheck
from horovod_tpu.common.message import RequestType

# Activity names (reference: common.h:30-51 macros).
ACT_QUEUE = "QUEUE"
ACT_MEMCPY_IN_FUSION_BUFFER = "MEMCPY_IN_FUSION_BUFFER"
ACT_COLLECTIVE = "COLLECTIVE"
ACT_MEMCPY_OUT_FUSION_BUFFER = "MEMCPY_OUT_FUSION_BUFFER"


class _NoOpTimeline:
    """Disabled timeline: every hook is a cheap no-op."""

    enabled = False
    dropped_events = 0

    def attach_drop_counter(self, counter): pass
    def set_world_cycle(self, n): pass
    def negotiate_start(self, name, request_type): pass
    def negotiate_rank_ready(self, name, rank): pass
    def negotiate_end(self, name, verdict=""): pass
    def negotiate_cached(self, fused=False): pass
    def wire_plan(self, detail): pass
    def start(self, name, op_name): pass
    def activity_start_all(self, names, activity, ts_ns=0): pass
    def activity_end_all(self, names, ts_ns=0): pass
    def end(self, name): pass
    def async_start(self, name, event_name, batch_id): pass
    def async_end(self, name, event_name, batch_id): pass
    def mark_cycle_start(self): pass
    def shutdown(self): pass


class Timeline(_NoOpTimeline):
    """Enabled timeline writing Chrome-tracing JSON."""

    enabled = True

    # Writer-queue bound: the writer drains to disk on its own thread,
    # and a slow or hung disk previously grew the unbounded queue
    # without limit (every event the job ever traced, resident). Past
    # this depth new events are DROPPED and counted — a lossy trace
    # from a sick disk beats an OOM'd training job.
    DEFAULT_QUEUE_CAPACITY = 1 << 16

    def __init__(self, path: str, mark_cycles: bool = False,
                 queue_capacity: int = DEFAULT_QUEUE_CAPACITY):
        self._path = path
        self.mark_cycles = mark_cycles
        self._queue: "queue.Queue[Optional[dict]]" = queue.Queue(
            maxsize=queue_capacity)
        self.dropped_events = 0
        # hvd_timeline_dropped_events_total mirror (metrics plane);
        # the runtime swaps in a real counter when metrics are on.
        self._drop_metric = None
        self._pids: Dict[str, int] = {}
        self._next_pid = 1
        self._wc = 0  # world cycle number (set_world_cycle)
        self._lock = lockdep.lock("timeline.Timeline._lock")
        # The program's spans' clock (common/trace.py), so that an
        # activity that coincides with a span takes the span's reading.
        self._start_ns = time.time_ns()
        self._writer = threading.Thread(target=self._write_loop,
                                        name="hvd-timeline-writer",
                                        daemon=True)
        self._writer.start()

    def attach_drop_counter(self, counter) -> None:
        self._drop_metric = counter

    def set_world_cycle(self, n: int) -> None:
        """The world-identical negotiation-round sequence number
        (common/trace.py): stamped into every span-opening event's
        args as ``wc`` so per-rank timeline files correlate with the
        merged world trace — and with each other — by eye, without
        the aggregator armed. A bare int store; the runtime updates
        it once per completed world round."""
        self._wc = n

    def _put(self, rec: dict) -> None:
        """Enqueue one event; on overflow drop it and count the drop
        (surfaced in the stall report and the metrics registry). The
        counter bump is racy-cheap on purpose: drops only happen when
        the writer is already wedged."""
        try:
            self._queue.put_nowait(rec)
        except queue.Full:
            self.dropped_events += 1
            if self._drop_metric is not None:
                self._drop_metric.inc()

    # -- writer thread (reference: timeline.h:46-74 TimelineWriter) ------
    def _write_loop(self):
        threadcheck.register_role("hvd-timeline-writer")
        with open(self._path, "w") as f:
            f.write("[\n")
            first = True
            while True:
                rec = self._queue.get()
                if rec is None:
                    break
                if not first:
                    f.write(",\n")
                f.write(json.dumps(rec))
                first = False
                f.flush()
            f.write("\n]\n")

    def _ts(self, ts_ns: int = 0) -> int:
        return ((ts_ns or time.time_ns()) - self._start_ns) // 1000

    def _pid(self, name: str) -> int:
        with self._lock:
            pid = self._pids.get(name)
            if pid is None:
                pid = self._next_pid
                self._next_pid += 1
                self._pids[name] = pid
                self._put({"name": "process_name", "ph": "M",
                           "pid": pid, "args": {"name": name}})
                self._put({"name": "process_sort_index", "ph": "M",
                           "pid": pid, "args": {"sort_index": pid}})
            return pid

    # Event phases that OPEN (or fully describe) a span get the world
    # cycle stamp; closing "E"/"e" events inherit it in the viewer, so
    # stamping them too would only bloat the file.
    _WC_PHASES = frozenset(("B", "X", "i", "b"))

    def _emit(self, ph: str, name: str, event_name: str, ts_ns: int = 0,
              **kw):
        rec = {"ph": ph, "pid": self._pid(name), "ts": self._ts(ts_ns)}
        if event_name:
            rec["name"] = event_name
        rec.update(kw)
        if ph in self._WC_PHASES:
            rec.setdefault("args", {})["wc"] = self._wc
        self._put(rec)

    # -- negotiation (reference: timeline.cc NegotiateStart/RankReady/End,
    # called from IncrementTensorCount, operations.cc:174-186) -----------
    def negotiate_start(self, name: str, request_type) -> None:
        op = RequestType(request_type).name
        self._emit("B", name, f"NEGOTIATE_{op}")

    def negotiate_rank_ready(self, name: str, rank: int) -> None:
        self._emit("X", name, f"{rank}", dur=0)

    def negotiate_end(self, name: str, verdict: str = "") -> None:
        # ``verdict`` names the resolved wire dtype so the span's end
        # carries the compression decision for this tensor.
        if verdict:
            self._emit("E", name, "", args={"wire": verdict})
        else:
            self._emit("E", name, "")

    def wire_plan(self, detail: str) -> None:
        """Instant marker naming a fused batch's stamped
        (algorithm, wire dtype) — NEGOTIATE_WIRE_PLAN in the trace."""
        self._emit("i", "cycle", f"NEGOTIATE_WIRE_PLAN {detail}",
                   s="g")

    def negotiate_cached(self, fused: bool = False) -> None:
        """Instant marker for a cycle negotiated entirely through the
        response-cache bitmask fast path — no per-tensor NEGOTIATE
        span exists on such cycles, so this is the trace's evidence
        of where negotiation time went (docs/performance.md).
        ``fused`` marks the speculative single-round variant, where
        the broadcast that followed this mark also carried the
        world-reduced data."""
        self._emit("i", "cycle",
                   "NEGOTIATE_CACHED_FUSED" if fused
                   else "NEGOTIATE_CACHED", s="g")

    # -- execution spans -------------------------------------------------
    def start(self, name: str, op_name: str) -> None:
        self._emit("B", name, op_name)

    def activity_start_all(self, names, activity: str,
                           ts_ns: int = 0) -> None:
        """``ts_ns``: the reading of the span that opens with the
        activity, where there is one; 0 reads the clock."""
        for name in names:
            self._emit("B", name, activity, ts_ns)

    def activity_end_all(self, names, ts_ns: int = 0) -> None:
        for name in names:
            self._emit("E", name, "", ts_ns)

    def end(self, name: str) -> None:
        self._emit("E", name, "")

    # -- async (deferred-close) spans -----------------------------------
    # Chrome/Perfetto ASYNC NESTABLE events ("b"/"e"), paired by
    # (category, id, name) instead of the per-pid B/E stack. Used for
    # collectives whose spans close at COMPLETION (async backends): a
    # tensor legally re-negotiates the same name while its previous
    # batch is still in flight, and deferred plain-E events would then
    # mispair with the new spans. The id is unique per (batch, TENSOR)
    # — viewers pair async events globally by (cat, id, name), not per
    # pid, so a batch-only id would merge a fused batch's N tensors
    # into one async tree and mispair their spans with each other.
    def _async_id(self, name: str, batch_id: int) -> str:
        return f"{batch_id}.{self._pid(name)}"

    def async_start(self, name: str, event_name: str,
                    batch_id: int) -> None:
        self._emit("b", name, event_name, cat="hvd",
                   id=self._async_id(name, batch_id))

    def async_end(self, name: str, event_name: str,
                  batch_id: int) -> None:
        self._emit("e", name, event_name, cat="hvd",
                   id=self._async_id(name, batch_id))

    def mark_cycle_start(self) -> None:
        if self.mark_cycles:
            self._emit("i", "cycle", "CYCLE_START", s="g")

    def shutdown(self) -> None:
        # A bounded queue can be full when the writer is wedged on a
        # sick disk: give the sentinel a short blocking window, then
        # give up — joining a stuck writer would hang teardown, and
        # the trace is already lossy at that point.
        try:
            self._queue.put(None, timeout=1.0)
        except queue.Full:
            pass
        self._writer.join(timeout=5.0)


def create_timeline(path: str, mark_cycles: bool = False):
    """Rank-0 only, like the reference (timeline.h:78-79)."""
    if not path:
        return _NoOpTimeline()
    return Timeline(path, mark_cycles)


NOOP_TIMELINE = _NoOpTimeline()
