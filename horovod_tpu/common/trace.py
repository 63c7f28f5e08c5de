"""World trace plane: clock-aligned cross-rank tracing, per-cycle
straggler attribution, and the crash flight recorder.

Every diagnostic surface this framework had before this module was
rank-local: the rank-0 timeline profiles one process, the stall
inspector reports one coordinator's table, the metrics plane sums
counters but keeps no event order. At scale the questions that matter
are cross-rank and clock-aligned — *which rank makes every cycle
slow*, and *what was the world doing in the seconds before it died?*
Four coupled pieces answer them:

* :class:`ClockSync` — NTP-style per-peer clock offset estimation
  piggybacked on existing control traffic: the coordinator's PING
  beacon supplies the (t1) send stamp, the worker's next TRACE frame
  echoes (t2, t3), and the frame's arrival supplies (t4). Offsets are
  smoothed by a minimum-RTT filter (congested samples are
  symmetric-delay violations and get discarded), maintained ON RANK 0
  — the coordinator clock is the world's reference frame.
* :class:`TraceCollector` / :class:`WorldTraceWriter` — every rank
  batches completed spans (bounded, drop-counted) into TAG_TRACE
  frames that ride the control tree out-of-band like METRICS frames;
  rank 0 writes ONE Chrome-trace (catapult) file with a track per
  rank, span timestamps corrected into the coordinator clock, and the
  world-identical cycle sequence number on every span
  (``HOROVOD_TPU_TRACE``, ``hvdtpurun --trace``).
* :class:`StragglerTracker` — the coordinator stamps per-rank arrival
  times at every negotiation gather (native paths included:
  ``hvd_gather_frames``/``hvd_steady_coord`` return per-peer
  CLOCK_MONOTONIC stamps) and attributes each cycle's critical path:
  ``hvd_cycle_skew_seconds``, per-rank arrival-lag max-gauges and a
  last-arriver counter per rank on the metrics plane, plus the
  stall-report line ("rank 3 last-arriver in 84% of the last 1000
  gathers").
* :class:`FlightRecorder` — a lock-cheap fixed-size ring of recent
  cycle/abort/elastic events per rank, ON BY DEFAULT (compiled-out
  no-op writes when ``HOROVOD_TPU_FLIGHT=0``, the NOOP_METRIC
  pattern), dumped to a postmortem JSONL on ``WorldAbortedError``,
  stall shutdown and SIGUSR2 — a production abort ships the last N
  seconds of world history with no profiling armed.

* :func:`span` / :func:`interval` — the program's ONE span call
  (``hvd.init`` ... ``hvd.complete``, the vocabulary in
  :data:`SPAN_COUNTS`): a ``jax.profiler.TraceAnnotation`` so an
  operator's profile shows the program's spans over the device's ops,
  one ``hvd_span_seconds{span=...}`` histogram family, a
  process-lifetime ring on the profiler's clock
  (:func:`recent_spans`), and the world trace's slices — armed with
  the registry or the world trace, the shared :data:`NOOP_SPAN`
  otherwise (docs/tracing.md).

The recorder and the clock table are process-lifetime singletons (the
lockdep pattern): they must survive elastic re-inits so a postmortem
spans world generations, and modules without a Runtime in hand
(common/elastic.py, common/faults.py) can still record.
"""

from __future__ import annotations

import json
import os
import queue
import signal
import threading
import time
from collections import OrderedDict, deque
from itertools import count
from typing import Dict, List, NamedTuple, Optional, Tuple

from horovod_tpu.common import config as hconfig
from horovod_tpu.common import lockdep
from horovod_tpu.common import threadcheck
from horovod_tpu.common import logging as hlog
from horovod_tpu.common.wire import (
    EV_ABORT, EV_CYCLE, EV_ELASTIC, EV_FAULT, EV_MARK, EV_NAMES,
    EV_SELFOP, EV_STALL, EV_TEARDOWN, SPAN_MARK, SPAN_SLICE,
    combine_trace_frames, parse_trace_frame, serialize_trace_frame,
)

__all__ = [
    "EV_CYCLE", "EV_ABORT", "EV_ELASTIC", "EV_STALL", "EV_FAULT",
    "EV_TEARDOWN", "EV_MARK", "EV_SELFOP", "ClockSync", "TraceCollector",
    "NOOP_TRACE", "FlightRecorder", "NOOP_RECORDER", "flight",
    "clock", "StragglerTracker", "WorldTraceWriter",
    "install_sigusr2", "serialize_trace_frame", "parse_trace_frame",
    "combine_trace_frames", "span", "interval", "NOOP_SPAN",
    "SPAN_COUNTS", "SpanRecord", "recent_spans", "spans_dropped",
    "arm_spans", "spans_armed", "DEVICE_SCOPES",
]


# ---------------------------------------------------------------------------
# Clock alignment
# ---------------------------------------------------------------------------

class _PeerClock:
    """Smoothed offset estimate for one peer: keep the recent samples
    and trust the one with the smallest round trip — queueing delay is
    the symmetric-delay violation that skews NTP math, and it only
    ever INFLATES the RTT, so min-RTT is the classic filter."""

    __slots__ = ("samples",)
    WINDOW = 32

    def __init__(self):
        self.samples: deque = deque(maxlen=self.WINDOW)

    def add(self, offset: float, rtt: float) -> None:
        self.samples.append((rtt, offset))

    def estimate(self) -> Optional[Tuple[float, float]]:
        """(offset_seconds, rtt_seconds) of the best recent sample, or
        None before any sample arrived. Offset is peer_clock minus
        coordinator_clock: coordinator time = peer time - offset."""
        if not self.samples:
            return None
        rtt, offset = min(self.samples)
        return offset, rtt


class ClockSync:
    """Both halves of the piggybacked clock exchange.

    Coordinator side: :meth:`ping_sent` records (seq -> t1) for every
    PING the beacon fans out; :meth:`echo` closes the loop when a
    worker's TRACE frame answers with (t2, t3) and the frame arrival
    supplies t4:

        rtt    = (t4 - t1) - (t3 - t2)
        offset = ((t2 - t1) + (t3 - t4)) / 2     # peer - coordinator

    Worker side: :meth:`ping_received` notes the latest coordinator
    PING (sender rank 0 only — local-root beacons carry their own
    clocks); :meth:`take_echo` hands the pending answer to the next
    TRACE frame build, consuming it so one ping is answered once.

    Thread-safety: pings arrive on the background loop, echoes are
    consumed there too, but rank 0's table is read from the stall
    report and the HTTP metrics thread — one small lock covers it.
    """

    PING_MEMORY = 128

    def __init__(self):
        self._lock = lockdep.lock("trace.ClockSync._lock")
        self._pings: "OrderedDict[int, float]" = OrderedDict()
        self._peers: Dict[int, _PeerClock] = {}
        self._pending_echo: Optional[Tuple[int, float]] = None

    def reset(self) -> None:
        """Forget every peer and outstanding ping. Elastic resizes
        renumber the survivors densely (common/elastic.py), so a
        per-rank offset table carried across generations would bind
        one host's clock skew to a DIFFERENT host's new rank —
        membership install calls this."""
        with self._lock:
            self._pings.clear()
            self._peers.clear()
            self._pending_echo = None

    # -- coordinator side ------------------------------------------------
    def ping_sent(self, seq: int, t1: float) -> None:
        with self._lock:
            self._pings[seq] = t1
            while len(self._pings) > self.PING_MEMORY:
                self._pings.popitem(last=False)

    def echo(self, rank: int, seq: int, t2: float, t3: float,
             t4: float) -> None:
        with self._lock:
            t1 = self._pings.get(seq)
            if t1 is None:
                return  # answer to a ping we forgot: drop
            rtt = (t4 - t1) - (t3 - t2)
            if rtt < 0:
                return  # clocks moved mid-sample (suspend?): garbage
            offset = ((t2 - t1) + (t3 - t4)) / 2.0
            peer = self._peers.get(rank)
            if peer is None:
                peer = self._peers[rank] = _PeerClock()
            peer.add(offset, rtt)

    def offsets(self) -> Dict[int, Tuple[float, float]]:
        """{rank: (offset_s, rtt_s)} for every peer with samples."""
        with self._lock:
            out = {}
            for r, peer in self._peers.items():
                est = peer.estimate()
                if est is not None:
                    out[r] = est
            return out

    def offset_of(self, rank: int) -> float:
        """Best offset for ``rank`` (0.0 = coordinator itself, or no
        sample yet — spans then align uncorrected, which is exactly
        the pre-PR behavior)."""
        if rank == 0:
            return 0.0
        with self._lock:
            peer = self._peers.get(rank)
        if peer is None:
            return 0.0
        est = peer.estimate()
        return est[0] if est is not None else 0.0

    # -- worker side -----------------------------------------------------
    def ping_received(self, sender_rank: int, seq: int,
                      t2: float) -> None:
        if sender_rank != 0:
            return  # only the coordinator clock is the reference
        with self._lock:
            self._pending_echo = (seq, t2)

    def take_echo(self) -> Optional[Tuple[int, float, float]]:
        with self._lock:
            pending = self._pending_echo
            self._pending_echo = None
        if pending is None:
            return None
        seq, t2 = pending
        return (seq, t2, time.monotonic())


_CLOCK: Optional[ClockSync] = None
_CLOCK_LOCK = lockdep.lock("trace._CLOCK_LOCK")


def clock() -> ClockSync:
    """The process-wide clock table (survives elastic re-inits — the
    offsets of a stable host stay useful across generations)."""
    global _CLOCK
    if _CLOCK is None:
        with _CLOCK_LOCK:
            if _CLOCK is None:
                _CLOCK = ClockSync()
    return _CLOCK


# ---------------------------------------------------------------------------
# Span collection (per rank)
# ---------------------------------------------------------------------------

class _NoOpTraceCollector:
    """Disabled collector: every hook is a cheap no-op, one shared
    instance so the disabled-path test can assert identity."""

    enabled = False
    dropped = 0

    def slice(self, name, ts, dur, cycle): pass
    def mark(self, name, ts, cycle): pass
    def drain(self): return [], 0


NOOP_TRACE = _NoOpTraceCollector()


class TraceCollector(_NoOpTraceCollector):
    """Bounded per-rank span buffer feeding TAG_TRACE frames. Appends
    are a lock + list append; past capacity new spans are DROPPED and
    counted (the drop count rides the next frame's section header) —
    a wedged control plane must never grow an unbounded span list."""

    enabled = True
    CAPACITY = 4096

    def __init__(self, capacity: int = CAPACITY, tenant: str = ""):
        self._lock = lockdep.lock("trace.TraceCollector._lock")
        self._capacity = capacity
        self._spans: List[tuple] = []
        # Tenant sub-worlds (common/tenancy.py) prefix every span name
        # with their tenant id so the merged world trace attributes
        # each round to its job ("jobA:ROUND" vs "ROUND").
        self._prefix = f"{tenant}:" if tenant else ""
        self.dropped = 0

    def _push(self, span: tuple) -> None:
        with self._lock:
            if len(self._spans) >= self._capacity:
                self.dropped += 1
                return
            self._spans.append(span)

    def slice(self, name: str, ts: float, dur: float,
              cycle: int) -> None:
        self._push((SPAN_SLICE, cycle, ts, dur, self._prefix + name))

    def mark(self, name: str, ts: float, cycle: int) -> None:
        self._push((SPAN_MARK, cycle, ts, 0.0, self._prefix + name))

    def drain(self):
        """-> (spans, dropped_since_last_drain)."""
        with self._lock:
            spans, self._spans = self._spans, []
            dropped, self.dropped = self.dropped, 0
        return spans, dropped


def create_collector(enabled: bool, tenant: str = ""):
    return TraceCollector(tenant=tenant) if enabled else NOOP_TRACE


# ---------------------------------------------------------------------------
# Program spans (per process, on the profiler's clock)
# ---------------------------------------------------------------------------

# The vocabulary: span name -> what its ``n`` counts ("" = nothing).
# Stable names — docs/tracing.md lists them with the layer metric each
# is for, and the chip benchmark's readers key on them.
SPAN_COUNTS = {
    "hvd.init": "ranks",
    "hvd.init.native": "",
    "hvd.init.rendezvous": "ranks",
    "hvd.init.runtime": "",
    "hvd.broadcast_parameters": "leaves",
    "hvd.allreduce_gradients": "leaves",
    "hvd.enqueue": "tensors",
    "hvd.synchronize": "",
    "hvd.queue_wait": "tensors",
    "hvd.cycle": "requests",
    "hvd.hold": "",
    "hvd.negotiate": "",
    "hvd.execute": "tensors",
    "hvd.pack": "",
    "hvd.unpack": "",
    "hvd.complete": "handles",
}

# The device side's vocabulary: scope name -> what it holds. A scope is
# a ``jax.named_scope`` the program opens or a flax module's name that
# stands for a part no ``named_scope`` names; either is a component of
# the ``op_name`` every instruction of a compiled step carries, and
# ``spmd.device_scopes`` gives an instruction the innermost one. Stable
# names: docs/tracing.md lists them with the layer metric that reads
# each, and the chip benchmark's readers key on them.
DEVICE_SCOPES = {
    "loss": "forward and backward of a step; what no scope inside it "
            "names (residual adds, a block's own norms)",
    "optimizer": "the update outside the backward's fusions",
    "exchange": "the gradients' pmean and the loss's; a collective that "
                "carries no scope",
    "lm_head_loss": "the chunked head loss: three vocabulary-sized "
                    "products a chunk",
    "embed": "the token lookup and its scatter-add into the table's "
             "gradient (flax name)",
    "attn": "TransformerLM's attention; GlmMoeLM's outside `mla` "
            "(flax name)",
    "mlp": "a dense feed-forward (flax name; a scope in Phi4FlashLM)",
    "mla": "latent attention: projections, norms, rotary, the flash "
           "call, the output projection",
    "moe": "the expert layer outside its named parts (flax name): the "
           "conditional over its row buffer's tiers, the grouped "
           "products' custom calls (the compiler leaves them no path)",
    "moe.route": "the float32 router, top k, gate weights",
    "moe.dispatch": "the sort by expert and the gather into expert order",
    "moe.experts": "the three grouped products and the SwiGLU between",
    "moe.combine": "gate weights and the gather back to token order",
    "moe.shared": "the shared expert and its gate",
    "mtp": "the multi-token module and its pass through the head",
    "gdn.proj": "the Gated DeltaNet's input and output projections",
    "gdn.conv": "its causal depthwise convolution and SiLU",
    "gdn.rule": "L2 norms, gates, running sums and the rule's two kernels",
    "gdn.gate": "the gated RMSNorm of the rule's output",
    "kda.proj": "Kimi delta attention's q, k, v and output projections",
    "kda.conv": "its causal depthwise convolution and SiLU",
    "kda.gate": "the decay gate's projection and bounded sigmoid, beta "
                "and the output gate's projection",
    "kda.rule": "L2 norms and the per-channel rule's two kernels",
    "kda.norm": "the RMSNorm of the rule's output a head, times its gate",
    "gated_attn": "gated grouped-head attention around its flash call",
    "shortconv.proj": "the gated short convolution's two projections",
    "shortconv.conv": "its two gates and the three causal depthwise taps",
    "normed_attn": "grouped-head attention with normalised q and k "
                   "around its flash call",
    "swa_attn": "grouped-head attention inside a sliding window, with "
                "its rotary, around its flash call",
    "nope_attn": "grouped-head attention over the whole prefix without "
                 "any positional signal, around its flash call",
    "post_norm": "a block's RMSNorms on the outputs of its mixer and of "
                 "its feed-forward, with the residual adds they feed",
    "ssm.proj": "Mamba's four projections",
    "ssm.conv": "its causal depthwise convolution and SiLU",
    "ssm.scan": "the relayouts and the selective scan's two kernels",
    "ssm.gate": "y * silu(z)",
    "gmu": "a gated memory unit",
    "diff_attn": "full differential attention around its flash calls",
    "diff_attn.window": "the same inside the sliding window",
    "diff_attn.cross": "the same onto the kept keys and values",
}

SPAN_METRIC = "hvd_span_seconds"
# Decades from 1 us: a handle wait that finds its handle done is a
# microsecond, a start-up broadcast seconds.
SPAN_BUCKETS = (1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0)
SPAN_RING_CAPACITY = 1 << 16


class SpanRecord(NamedTuple):
    """One closed span. ``start_ns``/``end_ns`` are ``time.time_ns()``:
    the clock the JAX profiler stamps host events with (an
    ``.xplane.pb`` holds them minus its ``profile_start_time``).
    ``thread`` is the thread's name ("" for an interval that is no
    thread's), ``parent`` the ``id`` of the innermost span open on the
    same thread (0 = none), ``cycle`` the world cycle number the
    spans of one exchange share (0 = not known when the span
    closed)."""
    name: str
    start_ns: int
    end_ns: int
    thread: str
    parent: int
    cycle: int
    counts: dict
    id: int


class _NoOpSpan:
    """Tracing off: the one object every site gets. Entering it reads
    no clock; sites may set ``cycle``/``n``/``nbytes``/``tag`` on it
    unguarded."""

    __slots__ = ()
    on = False
    start_ns = end_ns = 0

    def __enter__(self): return self
    def __exit__(self, *exc): return False
    def __setattr__(self, name, value): pass


NOOP_SPAN = _NoOpSpan()


class _NoAnnotation:
    def __init__(self, name): pass
    def __enter__(self): return self
    def __exit__(self, *exc): return False


class SpanRing:
    """Bounded ring of closed spans (the FlightRecorder pattern): past
    capacity the oldest are overwritten and counted as dropped."""

    def __init__(self, capacity: int = SPAN_RING_CAPACITY):
        self._lock = lockdep.lock("trace.SpanRing._lock")
        self._ring: List[Optional[SpanRecord]] = [None] * max(1, capacity)
        self._next = 0

    def push(self, rec: SpanRecord) -> None:
        with self._lock:
            self._ring[self._next % len(self._ring)] = rec
            self._next += 1

    def snapshot(self) -> List[SpanRecord]:
        """The spans kept, oldest first."""
        with self._lock:
            n, nxt = len(self._ring), self._next
            return [self._ring[i % n] for i in range(max(0, nxt - n), nxt)]

    @property
    def dropped(self) -> int:
        """How many spans were overwritten."""
        return max(0, self._next - len(self._ring))


class _ThreadSpans(threading.local):
    """A thread's open spans, and the world-trace collector of the
    runtime whose background loop the thread is (None elsewhere: the
    world trace is the loop's track, its cycle numbers in order)."""

    def __init__(self):
        self.stack: List["_Span"] = []
        self.cycle = 0          # note_cycle's: the round being worked for
        self.collector = None
        self.name = threading.current_thread().name


_TLS = _ThreadSpans()
_SPAN_IDS = count(1)
_SPANS_ON = False
_SPAN_RING: Optional[SpanRing] = None
_SPAN_REGISTRY = None          # the default world's registry, once bound
_SPAN_HISTS: Dict[str, object] = {}
# (name, seconds) of spans closed before a registry was bound: start-up's
# own, observed once the world it started has one.
_SPAN_UNOBSERVED: List[Tuple[str, float]] = []
_ANNOTATION = None             # jax.profiler.TraceAnnotation, once armed
_WALL_TO_MONO_S = 0.0          # TraceCollector's clock is time.monotonic()


def arm_spans(on: bool) -> None:
    """Arm (or, from ``hvd.init()`` alone, disarm) the span call for
    this process. ``hvd.init()`` decides from the configuration it
    reads, before its own first span; a runtime whose registry or
    world trace is on arms too (tenant worlds)."""
    global _SPANS_ON, _SPAN_RING, _ANNOTATION, _WALL_TO_MONO_S
    if on and _SPAN_RING is None:
        try:
            from jax.profiler import TraceAnnotation as _ANNOTATION
        except ImportError:
            # jax is an optional extra: without it there is no profiler
            # to show the spans in; the ring and the registry stand.
            _ANNOTATION = _NoAnnotation
        _WALL_TO_MONO_S = time.monotonic() - time.time()
        _SPAN_RING = SpanRing()
    _SPANS_ON = bool(on)


def spans_armed() -> bool:
    """Whether the span call is armed: what else the program keeps only
    under tracing (a step's executable, ``spmd.note_compiled``) asks."""
    return _SPANS_ON


def bind_span_registry(registry) -> None:
    """The registry ``hvd_span_seconds`` is observed in: the default
    world's, bound when its runtime is built (``hvd.init()`` unbinds
    the world's before: what start-up closes until then is observed
    at the binding)."""
    global _SPAN_REGISTRY
    _SPAN_REGISTRY = registry
    _SPAN_HISTS.clear()
    if registry is not None:
        for name, seconds in _SPAN_UNOBSERVED:
            _span_histogram(name).observe(seconds)
    del _SPAN_UNOBSERVED[:]


def _span_histogram(name: str):
    hist = _SPAN_HISTS.get(name)
    if hist is None:
        hist = _SPAN_HISTS[name] = _SPAN_REGISTRY.histogram(
            f'{SPAN_METRIC}{{span="{name}"}}',
            "wall time of the program's spans, by span "
            "(docs/tracing.md)", SPAN_BUCKETS)
    return hist


def bind_thread_collector(collector) -> None:
    """Called by a runtime's background loop on its own thread: spans
    closed there feed ``collector`` (the world trace)."""
    _TLS.collector = collector


class _Span:
    """An open span; see :func:`span`."""

    __slots__ = ("name", "cycle", "n", "nbytes", "tag", "also",
                 "start_ns", "end_ns", "id", "_parent", "_annotation")
    on = True

    def __init__(self, name, cycle, n, nbytes, tag, also):
        self.name, self.cycle, self.n = name, cycle, n
        self.nbytes, self.tag, self.also = nbytes, tag, also
        self.start_ns = self.end_ns = self._parent = 0
        self.id = next(_SPAN_IDS)

    def __enter__(self):
        tls = _TLS
        stack = tls.stack
        if stack:
            self._parent = stack[-1].id
            self.cycle = self.cycle or stack[-1].cycle
        else:
            self.cycle = self.cycle or tls.cycle
        stack.append(self)
        self._annotation = _ANNOTATION(self.name)
        self._annotation.__enter__()
        self.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.time_ns()
        self._annotation.__exit__(*exc)
        tls = _TLS
        tls.stack.pop()
        _close(self, tls.name, tls.collector)
        return False


def _close(sp: _Span, thread: str, collector) -> None:
    """Keep the closed span: ring, histograms, world trace."""
    counts = {}
    key = SPAN_COUNTS[sp.name]
    if key and sp.n:
        counts[key] = sp.n
    if sp.nbytes:
        counts["bytes"] = sp.nbytes
    if sp.tag:
        counts["tag"] = sp.tag
    _SPAN_RING.push(SpanRecord(sp.name, sp.start_ns, sp.end_ns, thread,
                               sp._parent, sp.cycle, counts, sp.id))
    seconds = (sp.end_ns - sp.start_ns) * 1e-9
    if _SPAN_REGISTRY is not None:
        _span_histogram(sp.name).observe(seconds)
    elif len(_SPAN_UNOBSERVED) < 64:
        _SPAN_UNOBSERVED.append((sp.name, seconds))
    if sp.also is not None:
        sp.also.observe(seconds)
    if collector is not None:
        collector.slice(f"{sp.name} {sp.tag}" if sp.tag else sp.name,
                        sp.start_ns * 1e-9 + _WALL_TO_MONO_S, seconds,
                        sp.cycle)


def span(name: str, cycle: int = 0, n: int = 0, nbytes: int = 0,
         tag: str = "", also=None):
    """The program's span call, a context manager. Armed, one call
    opens a ``jax.profiler.TraceAnnotation(name)``, and on close keeps
    a :class:`SpanRecord` in the process's ring, observes the duration
    in ``hvd_span_seconds{span=name}`` (and in ``also``, a histogram
    that timed the same window under an older name), and feeds the
    world trace where the thread is a runtime's background loop. Not
    armed it returns :data:`NOOP_SPAN`. ``n`` counts what
    :data:`SPAN_COUNTS` says for ``name``; ``cycle``, ``n``,
    ``nbytes`` and ``tag`` may be set on the span until it closes; a
    span opened without a cycle takes its parent's, or the round its
    thread last noted (:func:`note_cycle`)."""
    if not _SPANS_ON:
        return NOOP_SPAN
    return _Span(name, cycle, n, nbytes, tag, also)


def interval(name: str, start_ns: int, end_ns: int, cycle: int = 0,
             n: int = 0, nbytes: int = 0, tag: str = "") -> None:
    """A span that is no thread's (the wait of a batch in the queue):
    both ends on :func:`span`'s clock, no parent, no annotation."""
    if _SPANS_ON:
        sp = _Span(name, cycle, n, nbytes, tag, None)
        sp.start_ns, sp.end_ns = start_ns, end_ns
        _close(sp, "", _TLS.collector)


def note_cycle(cycle: int) -> None:
    """World round ``cycle`` completed on this thread (a runtime's
    background loop): the spans it has open belong to that round, and
    so do those it opens until the next."""
    if _SPANS_ON:
        tls = _TLS
        tls.cycle = cycle
        for open_span in tls.stack:
            open_span.cycle = cycle


def current_cycle() -> int:
    """The cycle a span opened on this thread now would get (0 = none):
    what work handed to another thread carries over."""
    tls = _TLS
    return tls.stack[-1].cycle if tls.stack else tls.cycle


def span_clock_ns() -> int:
    """A reading of the spans' clock for :func:`interval`, or 0 where
    spans are off (so that a site stamps nothing then)."""
    return time.time_ns() if _SPANS_ON else 0


def recent_spans() -> List[SpanRecord]:
    """The ring's spans in the order they closed. Process-lifetime: it
    outlives ``hvd.shutdown()`` and elastic re-inits."""
    return _SPAN_RING.snapshot() if _SPAN_RING is not None else []


def spans_dropped() -> int:
    """Spans the ring has overwritten; a reader that needs every span
    of a run gives no value when this is not 0."""
    return _SPAN_RING.dropped if _SPAN_RING is not None else 0


# ---------------------------------------------------------------------------
# Flight recorder (per rank, on by default)
# ---------------------------------------------------------------------------

class _NoOpRecorder:
    """Disabled recorder: record() is a no-op; dump() writes nothing.
    One shared instance (NOOP_RECORDER) so every instrumented write
    site is enumerable by identity in tests, like NOOP_METRIC."""

    enabled = False

    def record(self, ev, cycle=0, arg=None, note=""): pass
    def set_identity(self, rank): pass
    def note_world(self, world_id, tenant, rank): pass
    def events(self): return []
    def dump(self, cause="", origin=-1, path=None): return None


NOOP_RECORDER = _NoOpRecorder()


class FlightRecorder(_NoOpRecorder):
    """Fixed-size ring of recent world events. A write is one clock
    read + a lock + a slot store — cheap enough to stay on by default
    at one event per negotiation round. The ring never allocates
    after construction beyond the stored tuples themselves."""

    enabled = True

    def __init__(self, capacity: int = 512):
        self._lock = lockdep.lock("trace.FlightRecorder._lock")
        self._ring: List[Optional[tuple]] = [None] * max(8, capacity)
        self._next = 0
        self._rank = hconfig.env_int("HOROVOD_RANK", -1)
        # Tenant sub-worlds this process is a member of (tenancy.py):
        # world id -> {"tenant", "rank"}, carried in every dump header
        # so a postmortem can attribute events to jobs.
        self._worlds: dict = {}
        self._dumped = 0

    def set_identity(self, rank: int) -> None:
        """Current-world rank for dump headers (the LAUNCH identity
        from HOROVOD_RANK stays in the filename — stable across
        elastic renumbering)."""
        self._rank = rank

    def note_world(self, world_id: int, tenant: str,
                   rank: int) -> None:
        """Register a tenant sub-world this process joined (the
        default world keeps set_identity); the recorder is process-
        lifetime, so the header names every world it ever served."""
        with self._lock:
            self._worlds[f"{world_id:#010x}"] = {
                "tenant": tenant, "rank": rank}

    def record(self, ev: int, cycle: int = 0,
               arg: Optional[int] = None, note: str = "") -> None:
        entry = (time.monotonic(), ev, cycle, arg, note)
        with self._lock:
            self._ring[self._next % len(self._ring)] = entry
            self._next += 1

    def events(self) -> List[tuple]:
        """Chronological snapshot of the ring. The lock is acquired
        best-effort: ``dump()`` runs inside the SIGUSR2 handler, which
        Python delivers ON the main thread — if that thread is mid-
        ``record()`` and already holds the lock, blocking here would
        wedge the very process the signal is trying to postmortem. A
        torn read of one in-flight slot is an acceptable last resort."""
        got = self._lock.acquire(timeout=0.2)
        try:
            n = len(self._ring)
            start = self._next
            out = [self._ring[(start + i) % n] for i in range(n)]
        finally:
            if got:
                self._lock.release()
        return [e for e in out if e is not None]

    def dump(self, cause: str = "", origin: int = -1,
             path: Optional[str] = None) -> Optional[str]:
        """Append one postmortem block (header line + event lines) to
        the rank's flight file; returns the path. Never raises — this
        runs on abort/signal paths where nothing may be assumed."""
        try:
            if path is None:
                base = hconfig.env_str("HOROVOD_TPU_FLIGHT_DIR", ".")
                launch_rank = hconfig.env_int("HOROVOD_RANK",
                                              self._rank)
                path = os.path.join(
                    base, f"hvd-flight-rank{max(launch_rank, 0)}"
                          f".pid{os.getpid()}.jsonl")
            events = self.events()
            now_wall, now_mono = time.time(), time.monotonic()
            header = {
                "flight": 1, "ts": now_wall, "mono": now_mono,
                "rank": self._rank,
                "launch_rank": hconfig.env_int("HOROVOD_RANK", -1),
                "pid": os.getpid(), "cause": cause, "origin": origin,
                "events": len(events), "dump": self._dumped,
            }
            if self._worlds:
                header["worlds"] = dict(self._worlds)
            try:
                from horovod_tpu.common import elastic as _elastic
                header["generation"] = _elastic.generation()
            except Exception:
                pass
            try:
                header["build"] = build_info()
            except Exception:
                pass
            with open(path, "a") as f:
                f.write(json.dumps(header, separators=(",", ":"))
                        + "\n")
                for t, ev, cyc, arg, note in events:
                    rec = {"t": round(t, 6),
                           "ev": EV_NAMES.get(ev, ev), "cycle": cyc}
                    if arg is not None:
                        # `is not None`, not truthiness: rank 0 as an
                        # abort origin (and generation 0) are real args
                        rec["arg"] = arg
                    if note:
                        rec["note"] = note
                    f.write(json.dumps(rec, separators=(",", ":"))
                            + "\n")
            self._dumped += 1
            return path
        except Exception:
            return None


_FLIGHT = None
_FLIGHT_LOCK = lockdep.lock("trace._FLIGHT_LOCK")


def flight():
    """The process-wide flight recorder. Enabled by default; set
    ``HOROVOD_TPU_FLIGHT=0`` for the compiled-out no-op (every write
    site then holds/calls the shared NOOP_RECORDER). Capacity:
    ``HOROVOD_TPU_FLIGHT_EVENTS`` (default 512). Deliberately not a
    Config field — the recorder must exist before any Config snapshot
    does and survive elastic re-inits (the lockdep pattern)."""
    global _FLIGHT
    if _FLIGHT is None:
        with _FLIGHT_LOCK:
            if _FLIGHT is None:
                if hconfig.env_bool("HOROVOD_TPU_FLIGHT", True):
                    _FLIGHT = FlightRecorder(hconfig.env_int(
                        "HOROVOD_TPU_FLIGHT_EVENTS", 512))
                else:
                    _FLIGHT = NOOP_RECORDER
    return _FLIGHT


def _reset_for_tests() -> None:
    """Drop the singletons so a test can re-read the env."""
    global _FLIGHT, _CLOCK
    with _FLIGHT_LOCK:
        _FLIGHT = None
    with _CLOCK_LOCK:
        _CLOCK = None


def _reset_spans_for_tests(capacity: int = SPAN_RING_CAPACITY) -> None:
    """Disarm and start an empty ring of ``capacity``."""
    global _SPAN_RING
    arm_spans(True)
    _SPAN_RING = SpanRing(capacity)
    arm_spans(False)
    bind_span_registry(None)


_SIGUSR2_INSTALLED = False


def install_sigusr2() -> bool:
    """Dump the flight ring on SIGUSR2 — the live-postmortem poke for
    a job that looks wedged but has not aborted. Main-thread only
    (signal module contract); installation failure is non-fatal."""
    global _SIGUSR2_INSTALLED
    if _SIGUSR2_INSTALLED:
        return True
    try:
        def _handler(signum, frame):
            flight().dump(cause="SIGUSR2")
        signal.signal(signal.SIGUSR2, _handler)
        _SIGUSR2_INSTALLED = True
        return True
    except (ValueError, OSError, AttributeError):
        return False  # non-main thread / unsupported platform


# ---------------------------------------------------------------------------
# Build identity (the hvd_build_info satellite)
# ---------------------------------------------------------------------------

def _native_build_hash() -> str:
    try:
        import hashlib

        from horovod_tpu import native as _native
        so = getattr(_native, "_SO_PATH", None)
        if not so or not os.path.exists(so):
            return "none"
        h = hashlib.sha256()
        with open(so, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        return h.hexdigest()[:12]
    except Exception:
        return "unknown"


def knobs_digest() -> str:
    """Short digest over every armed HOROVOD* knob (name=value,
    sorted) — two dumps with the same digest ran the same config."""
    import hashlib
    items = sorted(f"{k}={v}" for k, v in os.environ.items()
                   if k.startswith("HOROVOD"))
    return hashlib.sha256("\n".join(items).encode()).hexdigest()[:12]


def build_info() -> Dict[str, str]:
    """{version, native .so hash, armed-knobs digest, kernel-feature
    flags} — the identity postmortems need to tell WHICH build
    produced a dump. ``flags`` decodes hvd_build_flags: bit0 io_uring
    compiled in (Makefile probe), bit1 io_uring usable at runtime,
    bit2 MSG_ZEROCOPY compiled in; "none" for a pre-reactor .so."""
    from horovod_tpu import __version__
    from horovod_tpu import native as _native
    f = _native.build_flags()
    names = [name for bit, name in
             ((1, "io_uring"), (2, "io_uring_rt"), (4, "zerocopy"))
             if f & bit]
    return {"version": __version__,
            "native": _native_build_hash(),
            "knobs": knobs_digest(),
            "flags": "+".join(names) if names else "none"}


# ---------------------------------------------------------------------------
# Straggler attribution (rank 0)
# ---------------------------------------------------------------------------

class StragglerTracker:
    """Per-cycle critical-path attribution from the coordinator's
    gather arrival stamps. ``note_gather`` runs on the background
    loop once per negotiation gather (only when the metrics or trace
    plane armed it); the report line and metric mirrors are read from
    other threads, so the window state sits under a small lock."""

    WINDOW = 1000

    def __init__(self, registry=None):
        from horovod_tpu.common import metrics as hmetrics
        reg = registry if registry is not None \
            else hmetrics.NOOP_REGISTRY
        self._reg = reg
        self._lock = lockdep.lock("trace.StragglerTracker._lock")
        self._window: deque = deque(maxlen=self.WINDOW)
        self._last_counts: Dict[int, int] = {}
        self._max_lag: Dict[int, float] = {}
        self._gathers = 0
        self._m_skew = reg.histogram(
            "hvd_cycle_skew_seconds",
            "per negotiation gather: last arrival minus first "
            "arrival (the cycle's straggler-induced critical path)",
            buckets=hmetrics.LATENCY_BUCKETS)
        self._m_lag: Dict[int, object] = {}
        self._m_last: Dict[int, object] = {}

    def _peer_metrics(self, r: int):
        lag = self._m_lag.get(r)
        if lag is None:
            from horovod_tpu.common import metrics as hmetrics
            lag = self._reg.gauge(
                f'hvd_arrival_lag_seconds{{peer="{r}"}}',
                "worst observed gather arrival lag of this peer "
                "behind the cycle's first arrival",
                agg=hmetrics.AGG_MAX)
            self._m_lag[r] = lag
            self._m_last[r] = self._reg.counter(
                f'hvd_last_arriver_total{{peer="{r}"}}',
                "negotiation gathers this peer arrived LAST in")
        return lag, self._m_last[r]

    def note_gather(self, arrivals: Dict[int, float]) -> None:
        """``arrivals``: rank -> coordinator-monotonic stamp of that
        rank's request frame completing. Under the hierarchical
        control plane the ranks are channel OWNERS (a local root
        answers for its host)."""
        if len(arrivals) < 1:
            return
        first = min(arrivals.values())
        last_rank, last_t = max(arrivals.items(),
                                key=lambda kv: (kv[1], kv[0]))
        skew = last_t - first
        self._m_skew.observe(skew)
        with self._lock:
            self._gathers += 1
            old = None
            if len(self._window) == self._window.maxlen:
                old = self._window[0]
            self._window.append(last_rank)
            self._last_counts[last_rank] = \
                self._last_counts.get(last_rank, 0) + 1
            if old is not None:
                self._last_counts[old] -= 1
            for r, t in arrivals.items():
                lag = t - first
                if lag > self._max_lag.get(r, -1.0):
                    self._max_lag[r] = lag
                    gauge, _ = self._peer_metrics(r)
                    gauge.set(lag)
        _, counter = self._peer_metrics(last_rank)
        counter.inc()

    def window_stats(self) -> Dict[str, object]:
        """Snapshot of the attribution window for the supervision
        policy (common/selfop.py): gather count, window occupancy,
        per-rank last-arriver counts and worst lags."""
        with self._lock:
            return {
                "window": len(self._window),
                "gathers": self._gathers,
                "last_counts": dict(self._last_counts),
                "max_lag": dict(self._max_lag),
            }

    def report_line(self) -> str:
        """'rank 3 last-arriver in 84% of the last 1000 gathers
        (max lag 120.0ms)' — worst offenders first, empty before any
        gather was stamped."""
        with self._lock:
            n = len(self._window)
            if n == 0:
                return ""
            worst = sorted(
                ((c, r) for r, c in self._last_counts.items() if c > 0),
                reverse=True)[:3]
            parts = []
            for c, r in worst:
                lag = self._max_lag.get(r, 0.0)
                parts.append(f"rank {r} last-arriver in "
                             f"{100.0 * c / n:.0f}% of the last "
                             f"{n} gathers (max lag "
                             f"{lag * 1000.0:.1f}ms)")
        return "; ".join(parts)


# ---------------------------------------------------------------------------
# The merged world trace (rank 0)
# ---------------------------------------------------------------------------

class WorldTraceWriter:
    """Rank 0's fold point for TAG_TRACE frames: one Chrome-trace
    (catapult) JSON file with a track ("process") per rank, span
    timestamps corrected into the coordinator clock via the
    ClockSync offset table, and the world cycle number in every
    span's args. Writer thread + bounded queue, exactly the Timeline
    discipline — a sick disk drops spans, never blocks the control
    plane."""

    QUEUE_CAPACITY = 1 << 16

    def __init__(self, path: str, clock_sync: Optional[ClockSync] = None):
        self._path = path
        self._clock = clock_sync if clock_sync is not None else clock()
        self._queue: "queue.Queue[Optional[dict]]" = queue.Queue(
            maxsize=self.QUEUE_CAPACITY)
        self._lock = lockdep.lock("trace.WorldTraceWriter._lock")
        self._t0 = time.monotonic()
        self._seen_ranks: set = set()
        self._last_ts: Dict[int, float] = {}
        self.dropped_events = 0
        self.spans_written = 0
        self._writer = threading.Thread(target=self._write_loop,
                                        name="hvd-worldtrace-writer",
                                        daemon=True)
        self._writer.start()

    def _put(self, rec: dict) -> None:
        try:
            self._queue.put_nowait(rec)
        except queue.Full:
            self.dropped_events += 1

    def _write_loop(self):
        threadcheck.register_role("hvd-worldtrace-writer")
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

    def _ensure_rank(self, rank: int) -> None:
        if rank in self._seen_ranks:
            return
        self._seen_ranks.add(rank)
        self._put({"name": "process_name", "ph": "M", "pid": rank,
                   "args": {"name": f"rank {rank}"}})
        self._put({"name": "process_sort_index", "ph": "M",
                   "pid": rank, "args": {"sort_index": rank}})

    def add_section(self, rank: int, spans, dropped: int = 0) -> None:
        """Write one rank's span batch, offset-corrected. The offset
        is sampled ONCE per batch and each track is clamped monotonic
        — a drifting estimate between batches must never make a
        rank's own track run backwards in the viewer."""
        if not spans and not dropped:
            return
        offset = self._clock.offset_of(rank)
        with self._lock:
            self._ensure_rank(rank)
            last = self._last_ts.get(rank, float("-inf"))
            for kind, cycle, ts, dur, name in spans:
                t = ts - offset - self._t0
                if t < last:
                    t = last
                last = max(last, t + max(dur, 0.0))
                rec = {"pid": rank, "tid": 0, "name": name,
                       "ts": int(t * 1e6),
                       "args": {"wc": cycle}}
                if kind == SPAN_MARK:
                    rec["ph"] = "i"
                    rec["s"] = "t"
                else:
                    rec["ph"] = "X"
                    rec["dur"] = int(max(dur, 0.0) * 1e6)
                self._put(rec)
                self.spans_written += 1
            if dropped:
                self._put({"pid": rank, "tid": 0, "ph": "i", "s": "t",
                           "name": f"TRACE_DROPPED {dropped}",
                           "ts": int(max(last, 0.0) * 1e6),
                           "args": {"dropped": dropped}})
            self._last_ts[rank] = last

    def ingest(self, owner_rank: int, payload: bytes) -> None:
        """A TAG_TRACE frame off the control tree (any thread that
        recvs control frames). Closes each section's clock-echo loop
        with THIS arrival stamp (t4), then writes its spans. A
        garbled frame is dropped — best-effort, like metrics."""
        t4 = time.monotonic()
        try:
            sections = parse_trace_frame(payload)
        except Exception:
            return
        for sec in sections:
            echo = sec.get("echo")
            if echo is not None:
                seq, t2, t3 = echo
                self._clock.echo(sec["rank"], seq, t2, t3, t4)
            self.add_section(sec["rank"], sec["spans"],
                             sec.get("dropped", 0))

    def close(self) -> None:
        try:
            self._queue.put(None, timeout=1.0)
        except queue.Full:
            pass
        self._writer.join(timeout=5.0)


def clock_offsets_line() -> str:
    """Human line for the stall report: per-peer offset estimates vs
    the coordinator clock ('rank 1 +0.8ms (rtt 0.3ms), ...'), empty
    before any echo closed."""
    offs = clock().offsets()
    if not offs:
        return ""
    parts = [f"rank {r} {o * 1000.0:+.1f}ms (rtt {rtt * 1000.0:.1f}ms)"
             for r, (o, rtt) in sorted(offs.items())]
    return ", ".join(parts)
# -- thread-affinity sanitizer (HOROVOD_TPU_THREADCHECK) ------------------
# No fixed owner: rebound under WorldTraceWriter._lock from whichever
# control-plane thread folds a rank's batch.
threadcheck.install(WorldTraceWriter, "spans_written",
                    "trace.WorldTraceWriter.spans_written")
