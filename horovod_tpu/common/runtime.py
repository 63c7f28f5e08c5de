"""The background coordination loop — heart of the runtime.

Python re-architecture of the reference's ``BackgroundThreadLoop`` /
``RunLoopOnce`` / ``PerformOperation``
(reference: horovod/common/operations.cc:662-955, 986-1338, 450-539):
one daemon thread per process paces a negotiation cycle every
``HOROVOD_CYCLE_TIME`` ms; each cycle drains this rank's request queue,
gathers all ranks' requests at the coordinator, fuses ready tensors
under the fusion threshold, broadcasts the agreed ResponseList, and
executes it through the backend priority list. Enqueue APIs return
immediately; completion flows back through per-entry callbacks
(reference: common.h:162 StatusCallback).

Hot-loop notes for TPU: the data plane executed here is an XLA
computation per fused response (see ops/xla_ops.py); this thread only
*issues* it, so the Python cycle overhead rides in the shadow of device
execution, like the reference's detached CUDA finalizer threads
(reference: ops/cuda_operations.cc:148-179).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

from horovod_tpu.common import arena as harena
from horovod_tpu.common import elastic as helastic
from horovod_tpu.common import faults
from horovod_tpu.common import lockdep
from horovod_tpu.common import threadcheck
from horovod_tpu.common import logging as hlog
from horovod_tpu.common import metrics as hmetrics
from horovod_tpu.common import overlap as hoverlap
from horovod_tpu.common import selfop
from horovod_tpu.common import steady as hsteady
from horovod_tpu.common import trace as htrace
from horovod_tpu.common import wire
from horovod_tpu.common import wire_dtype as _wd
from horovod_tpu.common.config import Config
from horovod_tpu.common.controller import Controller
from horovod_tpu.common.coordinator import (
    CACHEABLE_REQUESTS, CACHEABLE_RESPONSES, MessageTable, ResponseCache,
    StallInspector, construct_response, fuse_responses, iter_set_bits,
)
from horovod_tpu.common.invariants import world_coherent
from horovod_tpu.common.message import (
    CacheCycleRequest, CacheCycleResponse, DataType, Request, RequestList,
    RequestType, Response, ResponseList, ResponseType, datatype_size,
    datatype_to_numpy_dtype, numpy_dtype_to_datatype,
)
from horovod_tpu.common.status import (
    DUPLICATE_NAME_ERROR_FMT, SHUT_DOWN_ERROR, Status, WorldAbortedError,
    world_abort_message,
)
from horovod_tpu.common.tensor_table import (
    HandleManager, TensorTable, TensorTableEntry,
)
from horovod_tpu.common.timeline import (
    ACT_COLLECTIVE, ACT_QUEUE, NOOP_TIMELINE, create_timeline,
)
from horovod_tpu.ops.operation_manager import OperationManager


def _merge_tenant_worlds(world: Dict) -> Dict:
    """Fold the world views of every tenant whose coordinator lives
    in THIS process into a copy of the default world's view. Tenant
    series carry their tenant label, so the merge never collides;
    docs/multitenancy.md describes which surface shows which tenant."""
    from horovod_tpu.common import tenancy as _tenancy
    merged = dict(world)
    for t in _tenancy.tenants().values():
        rt = t._runtime
        agg = getattr(rt, "_aggregator", None) if rt is not None \
            else None
        if agg is None:
            continue
        try:
            agg.update_local(rt.metrics.snapshot())
            hmetrics.merge_into(merged, agg.world())
        except Exception:
            pass  # a tenant mid-teardown must not break the scrape
    return merged


class Runtime:
    """Process-global state + background thread
    (reference: HorovodGlobalState, common/global_state.h:33-136)."""

    def __init__(self, config: Config, controller: Controller,
                 op_manager: OperationManager,
                 parameter_manager=None):
        self.config = config
        self.controller = controller
        self.op_manager = op_manager
        self.tensor_table = TensorTable()
        self.handle_manager = HandleManager()
        self.parameter_manager = parameter_manager
        self.timeline = NOOP_TIMELINE
        if controller.rank == 0 and config.timeline_path:
            self.timeline = create_timeline(config.timeline_path,
                                            config.timeline_mark_cycles)
        op_manager.attach_timeline(self.timeline)
        # Tenancy (common/tenancy.py): a tenant sub-world stamps every
        # cycle frame with its world id (wire.stamp_world) and paces
        # its coordinator-bound cycles through the process-local
        # tenant scheduler lane bound by bind_tenant_lane. world_id 0
        # (the default world) keeps the wire byte-identical to every
        # earlier build and every hook a no-op.
        self._world_id = int(getattr(config, "world_id", 0))
        self._tenant = getattr(config, "tenant_name", "")
        # Lane binding races teardown (bind arrives from the tenant
        # attach path while an abort is unwinding on the background
        # loop): the lock makes bind-vs-unregister atomic and the
        # closed flag keeps a late bind from resurrecting a lane on a
        # dead runtime — the scheduler would hold it forever.
        self._lane_lock = lockdep.lock("runtime.Runtime._lane_lock")
        self._lane_closed = False
        self._tenant_lane = None
        self._dtypes: Dict[str, DataType] = {}
        # name -> elements per dim-0 row, for allgather fusion byte
        # accounting (reference: TotalByteSizeOfAllgatherOutput).
        self._slice_numels: Dict[str, int] = {}
        self._stall = StallInspector(
            controller.size,
            warning_time=config.stall_check_time_seconds,
            shutdown_time=config.stall_shutdown_time_seconds,
            disabled=config.stall_check_disable)
        # A completed negotiation clears its stall-warning record so a
        # RECURRING tensor name that stalls again warns again.
        self._message_table = MessageTable(
            on_remove=self._stall.tensor_completed) \
            if controller.rank == 0 else None
        # Async completion: backends that return InProgress complete on
        # detached finalizer threads while this loop keeps negotiating
        # (reference: cuda_operations.cc:148-179).
        self.finalizer = None
        if config.async_completion:
            from horovod_tpu.common.finalizer import Finalizer
            self.finalizer = Finalizer()
            op_manager.attach_finalizer(self.finalizer)
        self._shutdown_requested = threading.Event()
        self._done = threading.Event()
        self._teardown_started = False
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[Exception] = None
        # (origin_rank, cause) once the world has aborted: handles that
        # were in flight or are enqueued afterwards fail with a
        # structured WorldAbortedError instead of a generic shutdown.
        self._abort_info: Optional[tuple] = None
        # Lifetime count of executed responses (fault-injection op
        # triggers key off it to land failures squarely mid-collective).
        self._op_count = 0
        faults.load_env()
        # Autotune plumbing: bytes reduced this cycle.
        self._cycle_bytes = 0
        # Monotone id for async-nestable timeline batches.
        self._batch_seq = 0
        # Idle backoff: after _IDLE_GRACE empty cycles the loop ramps
        # its sleep toward config.idle_backoff_ms instead of spinning
        # the negotiation at full cycle rate forever (the reference
        # wakes every cycle_time_ms regardless, operations.cc:987-995 —
        # needless wakeups on a TPU host whose hot path is in-jit).
        # ``_wake`` snaps the loop awake the moment work arrives or
        # shutdown is requested, so pickup latency IMPROVES over a
        # fixed cycle; each rank's sleep is local, and a straggling
        # rank only delays the blocking gather, never deadlocks it.
        self._idle_cycles = 0
        self._cycle_count = 0  # lifetime cycles (observability/tests)
        self._wake = threading.Event()
        # Steady-state negotiation fast path: a world-coherent LRU of
        # negotiated responses; hit cycles exchange one bit per cache
        # slot instead of serialized Request lists (HOROVOD_CACHE_*,
        # docs/performance.md). All knobs must match across ranks —
        # the frame kinds and epochs fail fast on divergence.
        self._cache: Optional[ResponseCache] = None
        # The cache stays ON under autotune: cached replays would pin
        # every steady tensor to the (algorithm, wire dtype) verdict
        # of its FIRST negotiation, so whenever the tuner's active
        # combo changes the coordinator force-evicts every cached
        # allreduce verdict world-wide through the broadcast invalid
        # mask (_stale_plan_slots) — the tensors renegotiate under
        # the new plan and the tuner measures what it steers.
        if config.cache_enabled and config.cache_capacity > 0:
            # Elastic worlds seed the epoch from the world generation:
            # every post-resize rank starts at the SAME (bumped) epoch,
            # so the response cache, steady predictor, replay plans
            # and native steady plans of the old world all invalidate
            # through the existing epoch machinery.
            self._cache = ResponseCache(
                config.cache_capacity,
                epoch0=helastic.generation() << 32)
        # name -> (signature, dtype, slice_numel) recorded when a
        # cacheable request is sent the FULL way; consumed when its
        # negotiated response comes back and populates the cache.
        self._pending_sigs: Dict[str, tuple] = {}
        # (grant_mask, threshold) -> fused replay plan, valid for one
        # cache epoch: the steady state replays the SAME grant every
        # cycle, so the per-cycle fuse pass collapses to a dict hit.
        self._replay_plans: Dict[tuple, List[Response]] = {}
        self._replay_epoch = -1
        # (epoch, hit_mask) -> serialized cycle frame: steady-state
        # cycles send the SAME all-hit frame every time — skip
        # re-serializing it (epoch in the key invalidates on any
        # structural cache event).
        self._frame_memo: Dict[tuple, bytes] = {}
        # name -> monotonic time its cache hit first went un-granted;
        # after _BIT_DEMOTE_S the request falls back to the full path
        # so the coordinator's stall machinery (warnings, shutdown
        # blame) sees it exactly as it would without the cache.
        self._bit_pending_since: Dict[str, float] = {}
        self._cached_cycles = 0  # cycles negotiated purely via bitmask
        # Fused speculative cycle (HOROVOD_CACHE_SPECULATIVE): once a
        # pure-hit cycle is FULLY granted, its mask becomes a steady
        # prediction — the next identical cycle sends its pre-packed
        # fused allreduce buffers WITH the bitmask, and the coordinator
        # reduces inline and broadcasts grant + result in one frame:
        # negotiation + data plane in a single world round-trip. Any
        # deviation on any rank degrades that cycle to the classic
        # two-round cached path (the payload is simply ignored).
        # Under autotune, speculation is gated per-PHASE
        # (ParameterManager.spec_safe): live through the discrete
        # grid phase — so per-combo scores measure the DEPLOYMENT
        # regime, spec cycle included — and after convergence, but
        # off while the Bayesian phase steers fusion/cycle parameters
        # through full-response trailers that speculation would
        # starve. The gate is coordinator-side (a spec round needs
        # the coordinator's own bid), so a worker's view of the
        # phase never has to be synchronized.
        self._spec_ok = (self._cache is not None
                         and config.cache_speculative)
        # Recently fully-granted pure-hit masks -> their name sets
        # (insertion-ordered, capped): the steady-state predictions,
        # doubling as the burst-hold's (_absorb_burst) reference sets.
        # More than one set stays steady in real loops — double-
        # buffered training alternates two gradient buckets, periodic
        # metrics add an every-N-steps set — and each deserves the
        # fused round. Slot-based, so any structural cache event
        # (epoch move) invalidates them all. Epoch-coupled predictions
        # are world-replicated state: they may only move on broadcast
        # verdicts, which hvdlint's world-coherence analyzer enforces.
        self._steady: "OrderedDict[int, frozenset]" = \
            OrderedDict()  # hvdlint: world-replicated
        self._steady_epoch = -1  # hvdlint: world-replicated
        # The coordinator's effective fusion threshold, broadcast on
        # cached-cycle responses: replay and speculative packing must
        # fuse with the WORLD's value, not this rank's local config
        # (a divergent HOROVOD_FUSION_THRESHOLD would otherwise build
        # mismatched batches from the same grant). World-replicated:
        # only the broadcast verdict may move it.
        self._world_fusion_threshold = \
            config.fusion_threshold_bytes  # hvdlint: world-replicated
        # Wire-dtype compression (common/wire_dtype.py): this rank's
        # PROPOSAL, attached to every compressible allreduce Request;
        # the coordinator's resolved verdict rides each Response (and
        # the cache with it), so the applied dtype is world-coherent
        # by the same broadcast that makes the negotiation coherent.
        self._wire_propose = _wd.wire_code_of(config.compression)
        t = getattr(controller, "topology", None)
        self._multi_host = (t is not None
                            and t.local_size < t.size)
        # Algorithm/dtype policy consulted when stamping fused
        # responses (coordinator only): the autotuner when armed
        # (ParameterManager.plan — per-size-bucket tuned table), the
        # static config policy otherwise.
        if parameter_manager is not None:
            self._wire_policy = parameter_manager
            parameter_manager.configure_wire(
                self._wire_propose, self._multi_host, controller.size,
                shm_enabled=config.shm_enabled,
                ring_allowed=config.ring_threshold_bytes >= 0)
            # Overlap bucket count joins the discrete grid (measured
            # between the wire sweep and the BO phase) only when the
            # overlap tier can actually engage on this rank.
            parameter_manager.configure_overlap(
                config.overlap_inflight > 0)
        else:
            self._wire_policy = _wd.StaticWirePolicy(
                config.two_level, config.two_level_threshold_bytes,
                self._multi_host, shm_enabled=config.shm_enabled)
            if config.two_level and controller.rank == 0 \
                    and not (self._multi_host and config.shm_enabled):
                hlog.warning(
                    "HOROVOD_TWO_LEVEL=1 has no effect: the two-level"
                    " plane needs a multi-host world with the shm"
                    " data plane enabled (HOROVOD_TPU_SHM=1)")
        # Last stamped/applied (algorithm, wire dtype) — rank-local
        # observability for the stall report.
        self._last_wire_verdict = None
        # Last wire-plan revision this coordinator stamped under: a
        # bump means the tuner moved the active combo, and every
        # cached allreduce verdict is stale — force-evicted world-wide
        # on the next cycle (see _coordinate_cycle).
        self._wire_plan_rev = 0
        # mask -> consecutive speculative bids the world answered with
        # a CLASSIC full grant: everything was granted, yet the fused
        # round was refused — the signature of a peer that will never
        # speculate (HOROVOD_CACHE_SPECULATIVE off, or a plane
        # mismatch). After _SPEC_DENY_LIMIT denials the mask stops
        # speculating, so a blessed heterogeneous-knob world does not
        # ship (and discard) the full fused payload every step
        # forever. A transient dead round (grant 0) does not count,
        # and a completed fused cycle resets the mask's slate.
        self._spec_denied: Dict[int, int] = {}
        # (hit mask, fusion threshold) of steady sets whose bid THIS
        # rank's backend declined (fused_cycle_reducible said no): the
        # answer is a function of the replay plan's sizes and of the
        # backend that carries them, both fixed for the key within a
        # cache epoch, so later cycles decline without walking the
        # plan again. Cleared with _spec_denied when the epoch moves.
        self._spec_declined: set = set()
        # [(fused Response, entries, arrays)] per payload segment of
        # the spec frame in flight this cycle (build->apply, bg thread
        # only); None when the current cycle is not speculative.
        self._spec_inflight = None
        # Zero-copy native data plane (HOROVOD_TPU_ZERO_COPY,
        # common/steady.py): steady speculative cycles run as ONE
        # native call — pack into the persistent fusion arena, send
        # mask + fused payload via sendmsg, reduce in C, receive the
        # world result straight into a fresh per-step buffer. Only
        # engaged when the controller sits on a flat tier of the
        # control tree AND the native core is loaded; every deviation
        # falls back to the classic PR 3 path for that cycle, and the
        # wire format is byte-identical either way, so mixed
        # native/pure-Python worlds interoperate frame-for-frame.
        self._steady_native_ok = (config.zero_copy
                                  and self._spec_ok
                                  and controller.steady_native_ready())
        self._send_arena = harena.FusionArena()
        # -- overlap tier (HOROVOD_OVERLAP_*, common/overlap.py) -------
        # Bucketed ready-order dispatch + in-flight steady cycles: the
        # background loop SUBMITS packed zero-copy cycles to a
        # dedicated completion thread and immediately returns to
        # building the next bucket's frame, so collective wire time
        # hides under backward compute. Rank-local scheduling only —
        # the wire protocol is unchanged, heterogeneous knobs degrade
        # to the synchronous path. Cycles stay strictly FIFO on the
        # wire (one native call at a time on the runner thread), and
        # every world-replicated mutation still happens on THIS
        # thread, at drain, in submission order.
        self._overlap: Optional[hoverlap.OverlapRunner] = None
        self._overlap_chunk = max(0, config.overlap_chunk_bytes)
        if config.overlap_inflight > 0 and self._steady_native_ok:
            self._overlap = hoverlap.OverlapRunner(
                controller.steady_spec_cycle,
                config.overlap_inflight,
                on_complete=self._wake.set)
        self._overlap_seq = 0
        self._overlap_hold_deadline = None  # empty-queue hold expiry
        self._overlap_cycles = 0  # completed overlapped cycles
        self._overlap_buckets_submitted = 0
        # Submission-ordered masks of cycles in flight on the runner:
        # the world-coherent cycle ORDER — every rank submits the same
        # masks in the same (program) order, and verdicts apply in
        # that order at drain. Mutated only on broadcast-driven paths.
        self._inflight_masks: List[int] = []  # hvdlint: world-replicated
        # Steady predictor depth: each overlap bucket needs its own
        # steady mask to stay resident or speculation thrashes. Any
        # bucketing source counts — the static knob, a byte-derived
        # count, or the autotuner's choice (armed via overlap_inflight)
        # — and all of them are bounded by MAX_BUCKETS, so size for
        # that worst case whenever bucketing can engage at all.
        self._steady_cap = (2 * hoverlap.MAX_BUCKETS
                            if (self._overlap is not None
                                or config.overlap_buckets > 0
                                or config.overlap_bucket_bytes > 0
                                or config.overlap_inflight > 0)
                            else 8)
        # Intended bucket name-sets from bucketed grouped submissions
        # (rank-local scheduling hint; identical everywhere because
        # the split is a pure function of the identical submission):
        # _split_buckets peels pops at these boundaries from the very
        # first cycle, so each bucket negotiates — and learns its
        # steady mask — separately even when the training thread gets
        # ahead of the wire. Snapshot-swapped, never mutated in place
        # (enqueue threads write, the background thread reads).
        self._bucket_sets: frozenset = frozenset()
        # (mask, threshold) -> SteadyPlan, valid for one cache epoch.
        self._steady_plans: Dict[tuple, hsteady.SteadyPlan] = {}
        self._steady_plan_epoch = -1
        # (plan, packed buffers) for the native cycle in flight this
        # step (build->cycle, bg thread only).
        self._spec_steady = None
        self._native_steady_cycles = 0
        self._spec_cycles = 0  # cycles completed via the fused round
        self._spec_bids = 0    # speculative frames sent (observability)
        # Steady cycles whose bid this rank's backend declined from
        # the batch's size, before any payload was copied.
        self._spec_declines = 0
        # Hits the last cycle bid but the world did not grant, now
        # requeued: their peers were already granted and will not be
        # re-enqueued, so they must never trigger a burst hold.
        self._requeued_names: frozenset = frozenset()
        # Monotonic count of speculative bids the world answered with
        # a classic full grant (per-mask slates in _spec_denied reset
        # on success; observability wants the lifetime total).
        self._spec_denials_total = 0

        # -- metrics plane (HOROVOD_TPU_METRICS, common/metrics.py) ----
        # Disabled (the default) hands every call site the shared
        # no-op metric — same zero-overhead contract as _NoOpTimeline;
        # durations are observed by the program's spans (htrace.span),
        # which read no clock while neither plane is on.
        self.metrics = hmetrics.create_registry(config.metrics_enabled,
                                                tenant=self._tenant)
        self._metrics_on = bool(config.metrics_enabled)
        reg = self.metrics
        self._m_cycle_s = reg.histogram(
            "hvd_cycle_seconds", "negotiation cycle wall time")
        self._m_negotiation_s = reg.histogram(
            "hvd_negotiation_seconds",
            "request gather -> response broadcast round trip")
        self._m_cycles = reg.counter("hvd_cycles_total")
        self._m_cached_cycles = reg.counter(
            "hvd_cached_cycles_total",
            "cycles negotiated purely via the cache bitmask")
        self._m_spec_cycles = reg.counter(
            "hvd_fused_spec_cycles_total",
            "single-round fused speculative cycles completed")
        self._m_spec_bids = reg.counter("hvd_spec_bids_total")
        self._m_spec_declines = reg.counter(
            "hvd_spec_declines_total",
            "steady cycles whose speculative bid this rank's backend "
            "declined from the batch's size, before any copy")
        self._m_spec_denials = reg.counter("hvd_spec_denials_total")
        self._m_native_steady = reg.counter(
            "hvd_native_steady_cycles_total",
            "steady steps completed by the one-call native data plane")
        self._m_arena_bytes = reg.gauge(
            "hvd_arena_bytes",
            "capacity of the persistent fusion arenas on this rank")
        self._m_data_copies = reg.counter(
            "hvd_data_copies_total",
            "payload byte-object copies on fallback data paths "
            "(0 while the zero-copy plane is engaged)")
        # Wire-compression plane (same counter objects as the socket
        # backend's module hooks — the registry memoizes by name).
        self._m_wire_saved = reg.counter(
            "hvd_wire_bytes_saved_total",
            "payload bytes kept OFF the wire by the negotiated "
            "wire dtype (uncompressed minus wire size, per send)")
        self._m_comp_ratio = reg.histogram(
            "hvd_compression_ratio",
            "wire bytes / uncompressed bytes per compressed payload",
            hmetrics.RATIO_BUCKETS)
        # Overlap-tier plane (docs/performance.md Layer 5).
        self._m_overlap_fraction = reg.histogram(
            "hvd_overlap_fraction",
            "per overlapped cycle: fraction of its wire time hidden "
            "under compute (1.0 = the loop never blocked on it)",
            hmetrics.RATIO_BUCKETS)
        self._m_inflight = reg.gauge(
            "hvd_inflight_cycles",
            "steady cycles outstanding on the overlap runner",
            agg=hmetrics.AGG_MAX)
        self._m_overlap_buckets = reg.counter(
            "hvd_overlap_buckets_total",
            "gradient buckets submitted by bucketed grouped dispatch")
        self._m_overlap_cycles = reg.counter(
            "hvd_overlap_cycles_total",
            "steady cycles completed through the overlap runner")
        self._m_cache_hits = reg.counter("hvd_cache_hits_total")
        self._m_cache_misses = reg.counter("hvd_cache_misses_total")
        self._m_cache_evictions = reg.counter(
            "hvd_cache_evictions_total")
        self._m_cache_entries = reg.gauge("hvd_cache_entries")
        self._m_queue_depth = reg.gauge(
            "hvd_tensor_queue_depth",
            "in-flight collectives tabled on this rank")
        self._m_burst_hold_s = reg.counter(
            "hvd_burst_hold_seconds_total",
            "time spent absorbing enqueue bursts")
        self._m_idle_hold_s = reg.counter(
            "hvd_idle_hold_seconds_total",
            "time spent in the steady-state idle hold")
        self._m_timeline_dropped = reg.counter(
            "hvd_timeline_dropped_events_total")
        self._m_lock_inversions = reg.counter(
            "hvd_lockcheck_inversions_total",
            "lock-order inversions observed by the runtime lockdep "
            "(HOROVOD_TPU_LOCKCHECK; 0 when unarmed)")
        self._m_affinity_violations = reg.counter(
            "hvd_threadcheck_violations_total",
            "thread-affinity violations observed by the runtime "
            "sanitizer (HOROVOD_TPU_THREADCHECK; 0 when unarmed)")
        # -- elastic worlds (HOROVOD_ELASTIC, common/elastic.py) -----
        # The context survives re-inits; each new Runtime generation
        # mirrors its counters so resize history rides the PR 4 plane.
        self._elastic = helastic.context()
        self._elastic_last_poll = 0.0
        self._m_world_size = reg.gauge(
            "hvd_world_size",
            "current world size (max-aggregated: the world view IS "
            "the size)", agg=hmetrics.AGG_MAX)
        self._m_world_resizes = reg.counter(
            "hvd_world_resizes_total",
            "elastic re-rendezvous barriers run by this rank as the "
            "(elected) coordinator")
        self._m_elastic_rejoins = reg.counter(
            "hvd_elastic_rejoins_total",
            "workers admitted into a resized world by this rank's "
            "rendezvous barriers")
        self._m_rdzv_s = reg.histogram(
            "hvd_elastic_rendezvous_seconds",
            "wall time from entering elastic recovery to holding a "
            "new world assignment")
        # -- self-operation (HOROVOD_SELFOP, common/selfop.py) -------
        # Policy is process-lifetime (decision counters and demotion
        # memory span generations); the runtime wires its telemetry
        # and wake event into it each re-init.
        self._selfop_policy = selfop.ensure_policy(controller.rank)
        self._selfop_last_tick = 0.0
        selfop.install_signal_handler(self._wake.set)
        self._selfop_decision_metrics: Dict[str, object] = {}
        self._m_sync_s = reg.histogram(
            "hvd_rejoin_sync_seconds",
            "wall time of each fast rejoin state sync "
            "(common/selfop.py chunked tree broadcast)")
        self._m_sync_bytes = reg.counter(
            "hvd_rejoin_sync_bytes_total",
            "payload bytes this rank moved through fast rejoin syncs")
        self._m_ckpt_age = reg.gauge(
            "hvd_checkpoint_age_seconds",
            "age of this rank's newest committed async checkpoint "
            "shard (-1 before the first write)")
        # The fused speculative cycle bypasses OperationManager, so the
        # runtime owns its share of the allreduce op/byte totals (the
        # registry memoizes by name — these are the SAME counters the
        # OperationManager increments on the classic path).
        self._m_bytes_allreduced = reg.counter(
            "hvd_bytes_allreduced_total")
        self._m_ops_allreduce = reg.counter(
            'hvd_ops_total{op="allreduce"}')
        self.timeline.attach_drop_counter(self._m_timeline_dropped)
        controller.attach_metrics(reg)
        op_manager.attach_metrics(
            reg, lambda: self._world_fusion_threshold)
        # Rank-0 world aggregation + read surfaces: control-tree
        # METRICS frames fold here, exposed over Prometheus HTTP
        # (HOROVOD_TPU_METRICS_PORT), a JSONL snapshot log
        # (HOROVOD_TPU_METRICS_LOG) and horovod_tpu.metrics().
        self._aggregator = None
        self._metrics_http = None
        self._metrics_log = None
        self._metrics_last_pub = 0.0
        if self._metrics_on:
            reg.add_collector(self._collect_runtime_metrics)
            if controller.rank == 0:
                self._aggregator = hmetrics.WorldAggregator(
                    controller.size)
                controller.metrics_sink = self._aggregator.ingest
                if config.metrics_port >= 0:
                    world_fn = self._aggregator.world
                    if not self._world_id:
                        # The fleet's /metrics also scrapes its
                        # co-located tenants (series are
                        # tenant-labelled; see metrics_view).
                        world_fn = (lambda base=self._aggregator.world:
                                    _merge_tenant_worlds(base()))
                    self._metrics_http = hmetrics.MetricsHTTPServer(
                        world_fn, config.metrics_port,
                        host=config.metrics_addr)
                if config.metrics_log:
                    self._metrics_log = hmetrics.JsonlMetricsLog(
                        config.metrics_log)
            # Info-style build identity (value always 1; the labels
            # ARE the payload): postmortems and dashboards can tell
            # WHICH build + knob set produced a dump or a regression.
            bi = htrace.build_info()
            reg.gauge(
                f'hvd_build_info{{version="{bi["version"]}",'
                f'native="{bi["native"]}",knobs="{bi["knobs"]}",'
                f'flags="{bi["flags"]}"}}',
                "build identity: package version, native .so build "
                "hash, armed-knobs digest, kernel-feature flags "
                "(io_uring/zerocopy; value is always 1)",
                agg=hmetrics.AGG_MAX).set(1)

        # -- world trace plane (HOROVOD_TPU_TRACE, common/trace.py) ----
        # Flight recorder first: ON BY DEFAULT (no-op writes when
        # HOROVOD_TPU_FLIGHT=0), process-lifetime singleton so a
        # postmortem spans elastic generations.
        self._flight = htrace.flight()
        if self._world_id:
            # Tenant sub-world: the process-lifetime recorder keeps
            # the default world's rank identity; tenants register in
            # the header's worlds map instead.
            self._flight.note_world(self._world_id, self._tenant,
                                    controller.rank)
        else:
            self._flight.set_identity(controller.rank)
        htrace.install_sigusr2()
        # Span collection + the world-identical cycle sequence number.
        self._trace = htrace.create_collector(bool(config.trace_path),
                                              tenant=self._tenant)
        self._trace_on = self._trace.enabled
        # The program's spans (htrace.span) are armed with either
        # plane; hvd_span_seconds lives in the default world's registry.
        if self._metrics_on or self._trace_on:
            htrace.arm_spans(True)
        if not self._world_id:
            htrace.bind_span_registry(reg)
        # World cycle of the newest batch this rank began to execute:
        # what a span that closes once its handles are done (a handle
        # wait, hvd.allreduce_gradients) takes as its cycle.
        self.exec_cycle = 0
        self._world_cycle = 0
        self._trace_last_pub = 0.0
        self._trace_spans_sent = 0
        self._m_trace_spans = reg.counter(
            "hvd_trace_spans_total",
            "trace spans this rank shipped (or wrote, on rank 0) "
            "into the world trace plane")
        self._trace_writer = None
        # Straggler attribution lives on rank 0 and arms whenever
        # EITHER observability plane is on (the metrics series are
        # no-ops without the registry, but the stall-report line and
        # the merged trace both want the arrival stamps).
        self._straggler = None
        if controller.rank == 0:
            if self._trace_on:
                # An elastic re-init constructs a fresh writer over
                # the same knob; suffix post-resize generations so the
                # just-finalized trace of the ABORTED world — the
                # artifact worth inspecting — is never truncated.
                trace_path = config.trace_path
                try:
                    from horovod_tpu.common import elastic as _elastic
                    gen = _elastic.generation()
                except Exception:
                    gen = 0
                if gen:
                    trace_path = f"{trace_path}.gen{gen}"
                if self._world_id:
                    # A tenant's rank-0 writer must never share (and
                    # truncate) the default world's file — same
                    # collision class the .genN suffix solves for
                    # elastic re-inits.
                    trace_path = (f"{trace_path}."
                                  f"{self._tenant or hex(self._world_id)}")
                self._trace_writer = htrace.WorldTraceWriter(trace_path)
                controller.trace_sink = self._trace_writer.ingest
            if self._metrics_on or self._trace_on:
                self._straggler = htrace.StragglerTracker(reg)
                controller.attach_trace(
                    on_arrivals=self._straggler.note_gather)
        elif self._trace_on:
            # Workers: arm the clock-echo half (PING noting).
            controller.attach_trace()

    @property
    def _spec_enabled(self) -> bool:
        pm = self.parameter_manager
        return self._spec_ok and (pm is None or pm.spec_safe)

    @property
    def _steady_native(self) -> bool:
        return self._steady_native_ok and self._spec_enabled

    # -- lifecycle -------------------------------------------------------
    def start(self) -> None:
        self._thread = threading.Thread(target=self._background_loop,
                                        name="hvd-background",
                                        daemon=True)
        self._thread.start()

    def request_shutdown(self) -> None:
        self._shutdown_requested.set()
        self._wake.set()

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    @property
    def alive(self) -> bool:
        return (self._thread is not None and self._thread.is_alive()
                and not self._done.is_set())

    def _terminal_status(self) -> Status:
        """Status for work that can no longer run: a structured abort
        (naming the failed rank) when the world was torn down by the
        fail-fast protocol, the plain shutdown error otherwise."""
        if self._abort_info is not None:
            origin, cause = self._abort_info
            return Status.WorldAborted(origin, cause)
        return Status.Aborted(SHUT_DOWN_ERROR)

    # -- enqueue APIs (reference: operations.cc:1430-1549) ---------------
    def enqueue(self, request_type: RequestType, entry: TensorTableEntry,
                dtype: DataType, shape, prescale: float = 1.0,
                postscale: float = 1.0) -> Status:
        if self._done.is_set() or self._shutdown_requested.is_set():
            return self._terminal_status()
        req = Request(request_rank=self.controller.rank,
                      request_type=request_type,
                      tensor_type=dtype,
                      tensor_name=entry.tensor_name,
                      root_rank=entry.root_rank,
                      device=entry.device,
                      tensor_shape=shape,
                      prescale_factor=prescale,
                      postscale_factor=postscale,
                      wire_dtype=self._propose_wire(request_type,
                                                    dtype))
        entry.request_type = request_type
        if not self.tensor_table.add(entry, req,
                                     htrace.span_clock_ns()):
            return Status.InvalidArgument(
                DUPLICATE_NAME_ERROR_FMT
                % (request_type.name.lower(), entry.tensor_name))
        if self._done.is_set():
            # The loop exited between the liveness check and the add; the
            # shutdown fan-out may have missed this entry — reclaim it so
            # its handle cannot hang forever.
            if self.tensor_table.pop_entry_if_present(entry.tensor_name):
                return self._terminal_status()
        if self._tenant_lane is not None:
            # Backlog hint for the QoS scheduler: queued work makes
            # this tenant a contender NOW, not only once its cycle
            # loop reaches acquire (benign unlocked write — the
            # acquire path re-asserts it under the lock).
            self._tenant_lane.want = True
        if not self._wake.is_set():
            self._wake.set()  # snap an idle-backed-off loop awake
        return Status.OK()

    def enqueue_group(self, request_type: RequestType, items,
                      prescale: float = 1.0,
                      postscale: float = 1.0) -> Status:
        """Atomically enqueue several entries as one negotiation batch
        (the grouped-collective contract, later-Horovod
        ``grouped_allreduce``): every request enters the same
        RequestList on this rank, so a concurrent cycle tick cannot
        split the group, all members become ready in the same
        coordinator cycle, and compatible members fuse into ONE
        Response under the threshold. ``items`` is a list of
        (entry, dtype, shape)."""
        if self._done.is_set() or self._shutdown_requested.is_set():
            return self._terminal_status()
        pairs = []
        for entry, dtype, shape in items:
            req = Request(request_rank=self.controller.rank,
                          request_type=request_type,
                          tensor_type=dtype,
                          tensor_name=entry.tensor_name,
                          root_rank=entry.root_rank,
                          device=entry.device,
                          tensor_shape=shape,
                          prescale_factor=prescale,
                          postscale_factor=postscale,
                          wire_dtype=self._propose_wire(request_type,
                                                        dtype))
            entry.request_type = request_type
            pairs.append((entry, req))
        dup = self.tensor_table.add_all(pairs, htrace.span_clock_ns())
        if dup is not None:
            return Status.InvalidArgument(
                DUPLICATE_NAME_ERROR_FMT
                % (request_type.name.lower(), dup))
        if self._done.is_set():
            # Same liveness race as enqueue(): reclaim anything the
            # shutdown fan-out may have missed. Per-entry, because the
            # fan-out may already have completed some members — their
            # callbacks must not fire twice.
            for entry, _ in pairs:
                if self.tensor_table.pop_entry_if_present(
                        entry.tensor_name) and entry.callback:
                    entry.callback(self._terminal_status())
        if self._tenant_lane is not None:
            self._tenant_lane.want = True  # backlog hint (see enqueue)
        if not self._wake.is_set():
            self._wake.set()
        return Status.OK()

    def _propose_wire(self, request_type: RequestType,
                      dtype: DataType) -> int:
        """This rank's wire-dtype bid for one request: the configured
        compression for float32/float64 allreduces (the gradient
        path), allgathers and reducescatters — every payload-moving
        collective with a meaningful reduced-precision rendering —
        none for everything else. The coordinator min-resolves the
        world's bids per tensor (and degrades int8 allgathers to bf16,
        since a concatenated world blob cannot carry per-rank scales),
        so a divergent knob degrades the verdict instead of the
        world."""
        if self._wire_propose and dtype in _wd.COMPRESSIBLE \
                and request_type in (RequestType.ALLREDUCE,
                                     RequestType.ALLGATHER,
                                     RequestType.REDUCESCATTER):
            return self._wire_propose
        return _wd.WIRE_NONE

    def _resolve_abort(self, origin: int, cause: str) -> tuple:
        """A blame inferred from an anonymous transport error can race
        the AUTHORITATIVE notice from the rank that actually detected
        the failure — its teardown closes channels, which peers see as
        a second, misattributable failure (a ring survivor names its
        dead neighbor and collapses; this rank only sees the
        survivor's close). Sweep the control plane for a
        queued/just-arriving ABORT and defer to it — the whole world
        then converges on one origin. Failure path only; adds nothing
        to healthy cycles."""
        try:
            notice = self.controller.drain_abort_notice(0.25)
        except Exception:
            notice = None
        return notice if notice is not None else (origin, cause)

    def _data_plane_abort(self, entries, origin: int,
                          cause: str) -> WorldAbortedError:
        """Fail a mid-collective batch as a world abort: resolve the
        origin against the control plane FIRST (the callbacks complete
        user-visible handles — they must carry the converged origin),
        fire the callbacks, and return the error for the caller to
        raise into the loop-level handler."""
        origin, cause = self._resolve_abort(origin, cause)
        status = Status.WorldAborted(origin, cause)
        for en in entries:
            if en.callback:
                en.callback(status)
        err = WorldAbortedError(world_abort_message(origin, cause),
                                origin_rank=origin, cause=cause)
        err.resolved = True  # _fail_world: don't re-drain
        return err

    def _fail_world(self, origin: int, cause: str,
                    resolved: bool = False) -> None:
        """Record the world abort and fan the notice to every
        reachable peer (see _resolve_abort for why an unresolved blame
        is checked against the control plane before committing)."""
        if not resolved:
            origin, cause = self._resolve_abort(origin, cause)
        self._error = WorldAbortedError(
            world_abort_message(origin, cause), origin_rank=origin,
            cause=cause)
        self._abort_info = (origin, cause)
        hlog.error(f"horovod_tpu world aborted: {self._error}",
                   rank=self.controller.rank)
        self._flight.record(htrace.EV_ABORT, self._world_cycle,
                            arg=origin, note=cause[:200])
        if self._trace_on:
            self._trace.mark("ABORT", time.monotonic(),
                             self._world_cycle)
        try:
            self.controller.abort(origin, cause)
        except Exception:
            pass
        # Postmortem AFTER the abort fan-out: file I/O must not delay
        # the notice the survivors' deadlines are waiting on. The dump
        # ships the last N seconds of world history (final cycles,
        # the abort, any elastic/stall events) with nothing armed.
        self._flight.dump(cause=cause, origin=origin)

    # -- the loop --------------------------------------------------------
    def _background_loop(self) -> None:
        threadcheck.register_role("hvd-background")
        htrace.bind_thread_collector(self._trace if self._trace_on
                                     else None)
        try:
            while self._run_loop_once():
                pass
        except WorldAbortedError as e:
            # Either received over the wire (a peer initiated the
            # abort) or raised locally (we detected the failure). Fan
            # the notice to every peer we can still reach — relays are
            # idempotent, so re-fanning a received abort is harmless —
            # then fail everything in flight with the structured error.
            # The BARE cause travels/persists, so each hop wraps the
            # origin banner exactly once.
            self._fail_world(e.origin_rank, getattr(e, "cause", str(e)),
                             resolved=getattr(e, "resolved", False))
        except (ConnectionError, OSError, TimeoutError) as e:
            # Transport failure nobody upstream could name: this rank
            # is the origin as far as the rest of the world knows.
            rank = self.controller.rank
            self._fail_world(rank,
                             f"transport failure on rank {rank}: {e}")
        except Exception as e:  # backend bug, ...
            self._error = e
            hlog.error(f"horovod_tpu background loop failed: {e!r}",
                       rank=self.controller.rank)
        finally:
            self._teardown()

    def _teardown(self) -> None:
        """Tear the runtime down — re-entrant AND stage-guarded.

        Re-entrant: the background loop's ``finally`` calls this, and
        elastic recovery (common/elastic.py) may call it again while
        draining a dead world; a SECOND abort raised during recovery
        (e.g. a WorldAbortedError surfacing from a native
        hvd_steady_worker/hvd_steady_coord teardown path) must find a
        no-op here, not a half-closed runtime whose finalizer drain
        wedges on re-entry. Stage-guarded: a raising finalizer drain
        or user completion callback must not skip the stages after it
        — in particular the timeline flush, or the trace of exactly
        the aborted runs you most want to inspect is left an
        unterminated JSON fragment."""
        if getattr(self, "_teardown_started", False):
            return
        self._teardown_started = True
        self._flight.record(htrace.EV_TEARDOWN, self._world_cycle)
        self._done.set()
        # Tenant lane first (stage-guarded): a dying tenant must stop
        # counting as a scheduling contender, or its co-tenants would
        # defer against a ghost until its user-level shutdown ran.
        with self._lane_lock:
            lane, self._tenant_lane = self._tenant_lane, None
            self._lane_closed = True
        # unregister OUTSIDE the lane lock: the scheduler takes its
        # own lock, and the attach path (scheduler -> bind_tenant_lane
        # -> lane lock) already fixes the opposite nesting order.
        if lane is not None:
            try:
                from horovod_tpu.common import tenancy as _tenancy
                _tenancy.scheduler().unregister(lane)
            except Exception:
                pass
        # Overlap runner first: its thread may sit inside a native
        # cycle against channels about to close — stop accepting work,
        # let the armed recv deadline return the call, and join. Any
        # undrained cycle's entries are still tabled (pops happen at
        # drain), so the pop_all below fails them with the terminal
        # status like everything else in flight.
        if self._overlap is not None:
            try:
                self._overlap.stop()
            except Exception:
                pass  # stage-guarded: plans must still drop
        # Native steady state next: the plans' cached ctypes bundles
        # bind file descriptors and arena generations of the world
        # that just died — drop them before anything that could raise,
        # so a resumed (elastic) process can never replay a stale
        # plan against rebuilt channels.
        try:
            self._spec_steady = None
            self._spec_inflight = None
            self._steady_plans.clear()
        except Exception:
            pass  # stage-guarded: the finalizer must still drain
        try:
            # Drain in-flight async completions first so every
            # issued collective fires its real status, then fail
            # what was never issued (reference:
            # operations.cc:898-913).
            if self.finalizer is not None:
                self.finalizer.drain()
        except Exception as e:
            hlog.warning(f"finalizer drain failed at shutdown: "
                         f"{e!r}", rank=self.controller.rank)
        terminal = self._terminal_status()
        for entry in self.tensor_table.pop_all():
            if entry.callback:
                try:
                    entry.callback(terminal)
                except Exception:
                    pass  # user callback; teardown must continue
        try:
            self.timeline.shutdown()
        except Exception:
            pass
        # Flush the trace tail: rank 0 writes its residue and closes
        # the merged file (the JSON array must terminate — the trace
        # of exactly the aborted run is the one worth inspecting);
        # workers best-effort ship theirs while the channel may still
        # be up. Stage-guarded like everything else here.
        if self._trace_on:
            try:
                spans, dropped = self._trace.drain()
                if self._trace_writer is not None:
                    self._trace_writer.add_section(0, spans, dropped)
                    self._trace_spans_sent += len(spans)
                elif (spans or dropped or
                      getattr(self.controller, "_child_trace", None)):
                    # a local root whose own buffer drained empty must
                    # still flush its children's parked frames — the
                    # tail of an aborted run is the part worth having
                    self.controller.send_trace(
                        wire.serialize_trace_frame(
                            [{"rank": self.controller.rank,
                              "dropped": dropped,
                              "echo": htrace.clock().take_echo(),
                              "spans": spans}]))
                    self._trace_spans_sent += len(spans)
            except Exception:
                pass
        if self._trace_writer is not None:
            try:
                self._trace_writer.close()
            except Exception:
                pass  # stage-guarded: metrics/backends must still close
        if self._aggregator is not None \
                and self._metrics_log is not None:
            # Final JSONL line with rank 0's own totals exact and
            # every owner's last-received frame folded in (workers
            # tear down concurrently, so their tail interval is
            # inherently best-effort — the log is a sampled view;
            # live exactness is the API/endpoint's job).
            try:
                self._aggregator.update_local(
                    self.metrics.snapshot())
                self._metrics_log.append(self._aggregator.world())
            except Exception:
                pass
        if self._metrics_http is not None:
            try:
                self._metrics_http.close()
            except Exception:
                pass  # stage-guarded: backends must still close
        try:
            self.op_manager.close()
        except Exception:
            pass  # stage-guarded: the controller must still close
        try:
            self.controller.close()
        except Exception:
            pass

    _IDLE_GRACE = 16  # empty cycles before the backoff ramp starts

    # How long a cache hit may stay un-granted (some rank has not
    # queued that tensor yet) before it falls back to the full
    # negotiation path. Bit-queued requests never enter the
    # coordinator's MessageTable, so without this demotion a tensor a
    # rank stops submitting would stall silently — invisible to the
    # stall inspector's warnings and shutdown blame. Healthy
    # steady-state hits are granted within a cycle or two; 5 s is
    # unreachable there and negligible next to the stall thresholds.
    _BIT_DEMOTE_S = 5.0

    # Consecutive classic-full-grant answers to speculative bids of
    # one mask before that mask stops speculating (see _spec_denied).
    _SPEC_DENY_LIMIT = 3

    # Empty-queue hold while steady state is established: how long an
    # idle rank waits for its producer before initiating an empty
    # (grant-nothing) round. Capped by heartbeat_timeout/4 so a
    # silently-holding rank can never be mistaken for a dead one.
    _STEADY_IDLE_S = 0.25

    # Floor for the burst hold's total budget (_absorb_burst): the
    # hold waits at most max(2 x cycle_time, this) for the rest of the
    # step's enqueue burst, woken by each enqueue rather than by
    # polling. Generous on purpose: while a rank holds, the world is
    # blocked in the request gather waiting for its frame anyway, so
    # the hold adds latency ONLY when the steady set genuinely shrank
    # — which pays this once and then re-learns the smaller set from
    # its next grant. A fragment negotiated instead would cost far
    # more: a mispredicted speculative cycle plus an extra
    # negotiation + data round for the remainder.
    _BURST_HOLD_S = 0.02

    def _bounded_hold_s(self, multiple: float, floor_s: float,
                        cycle_ms: Optional[float] = None) -> float:
        """A hold/wait budget derived from the cycle time, clamped as
        a WHOLE under heartbeat_timeout/4: a silently-holding rank
        sends no frames, and its only proof of life is its next one —
        every hold in this loop must stay far under the peer-death
        deadline, whatever HOROVOD_CYCLE_TIME is set to. THE one
        budget rule for the burst hold, the steady idle hold and the
        overlap empty-queue hold. ``cycle_ms`` overrides the config
        value where the autotuner's tuned cycle time governs."""
        if cycle_ms is None:
            cycle_ms = self.config.cycle_time_ms
        hold = max(multiple * cycle_ms / 1000.0, floor_s)
        hb = self.config.heartbeat_timeout_s
        if hb > 0:
            hold = min(hold, hb / 4.0)
        return hold

    # -- tenancy (common/tenancy.py) -------------------------------------
    def bind_tenant_lane(self, lane) -> None:
        """Attach this runtime's lane in the process-local tenant
        scheduler: cycles with local work acquire the lane (QoS-
        weighted interleave + quota deferral, bounded far under the
        heartbeat deadline) and report their negotiated bytes back."""
        with self._lane_lock:
            if self._lane_closed:
                # Teardown already unwound: binding now would leave the
                # scheduler holding a lane no cycle loop will ever pace.
                return
            self._tenant_lane = lane

    def _stamp(self, frame: bytes) -> bytes:
        return wire.stamp_world(frame, self._world_id) \
            if self._world_id else frame

    def _unstamp(self, frame: bytes) -> bytes:
        return wire.unstamp_world(frame, self._world_id) \
            if self._world_id else frame

    def _build_request_frame(self, requests: List[Request],
                             shutting_down: bool):
        """Partition this cycle's requests into cache-bitmask bits and
        full Requests; returns (payload, bit_requests) where
        ``bit_requests`` is [(slot, request)] for the hits the grant
        mask will adjudicate."""
        cache = self._cache
        self._spec_inflight = None
        if cache is None:
            return self._stamp(wire.serialize_cycle_request(
                RequestList(requests, shutdown=shutting_down))), []
        now = time.monotonic()
        hit_mask = 0
        invalid_mask = 0
        uncached: List[Request] = []
        bit_requests: List[tuple] = []
        for req in requests:
            state, slot = cache.lookup(req)
            if state == ResponseCache.HIT:
                pending = self._bit_pending_since.get(req.tensor_name)
                if pending is None or \
                        now - pending < self._BIT_DEMOTE_S:
                    hit_mask |= 1 << slot
                    bit_requests.append((slot, req))
                    continue
                # Un-granted for too long: demote to the full path so
                # the coordinator's stall machinery sees it.
                self._bit_pending_since.pop(req.tensor_name, None)
                hlog.warning(
                    f"tensor {req.tensor_name} waited "
                    f"{now - pending:.1f}s as a cached hit without "
                    f"world agreement; falling back to full "
                    f"negotiation", rank=self.controller.rank)
            elif state == ResponseCache.INVALID:
                invalid_mask |= 1 << slot
            self._record_signature(req)
            uncached.append(req)
        if not uncached and not invalid_mask and not shutting_down:
            if hit_mask and self._spec_enabled \
                    and self._steady_epoch == cache.epoch \
                    and hit_mask in self._steady \
                    and self._spec_denied.get(hit_mask, 0) \
                    < self._SPEC_DENY_LIMIT:
                payload = self._build_spec_frame(hit_mask, bit_requests)
                if payload is not None:
                    return payload, bit_requests
            # Pure-hit (or empty) frame: bit-identical every
            # steady-state cycle — serialize once per (epoch, mask).
            key = (cache.epoch, hit_mask)
            payload = self._frame_memo.get(key)
            if payload is None:
                payload = self._stamp(wire.serialize_cycle_request(
                    CacheCycleRequest(
                        epoch=cache.epoch, nslots=cache.nslots,
                        hit_mask=hit_mask)))
                if len(self._frame_memo) >= 64:
                    self._frame_memo.clear()
                self._frame_memo[key] = payload
            return payload, bit_requests
        payload = self._stamp(wire.serialize_cycle_request(
            CacheCycleRequest(
                epoch=cache.epoch, nslots=cache.nslots,
                hit_mask=hit_mask, invalid_mask=invalid_mask,
                requests=uncached, shutdown=shutting_down)))
        return payload, bit_requests

    def _absorb_burst(self, requests: List[Request]) -> List[Request]:
        """Hold a cycle that caught the FRONT of an enqueue burst: a
        training step submits the steady-state set back-to-back, and a
        loop that negotiates the first fraction gets a fragment grant —
        the step's one fused batch splits into several data-plane
        rounds, every cycle re-bids the remainder, and each fragment
        pays full round-trip cost. While the popped names are all
        cache hits forming a strict subset of the last granted cycle's
        set, wait (bounded by one cycle period) for the rest of the
        burst; any non-steady name or the deadline ends the hold — a
        transition cycle pays at most one cycle_time_ms of extra
        latency, the bound pacing already imposes."""
        steady_sets = self._steady.values()
        if not steady_sets:
            return requests
        seen = {r.tensor_name for r in requests}

        def fragment() -> bool:
            # A strict subset of SOME steady set — and not exactly any
            # of them (a complete bucket must negotiate now, even if
            # it happens to sit inside a larger steady set).
            return (not any(seen == s for s in steady_sets)
                    and any(seen < s for s in steady_sets))

        if not fragment() or seen <= self._requeued_names:
            return requests
        deadline = time.monotonic() + self._bounded_hold_s(
            2, self._BURST_HOLD_S)
        with htrace.span("hvd.hold", tag="burst") as hold:
            while True:
                # Event-driven, not polled: clear BEFORE draining so an
                # enqueue that lands between the drain and the wait
                # still sets the event (no missed wake, no busy spin —
                # an earlier 0.5 ms polling variant of this hold cost
                # more GIL contention than the fragmentation it
                # prevented).
                self._wake.clear()
                more = self.tensor_table.pop_messages()
                if more:
                    requests.extend(more)
                    seen.update(r.tensor_name for r in more)
                    if not fragment():
                        break
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0 or self._shutdown_requested.is_set():
                    break
                self._wake.wait(remaining)
        self._m_burst_hold_s.inc((hold.end_ns - hold.start_ns) * 1e-9)
        return requests

    @staticmethod
    def _batch_nbytes(entries, bit_requests) -> int:
        """Uncompressed input bytes of a batch from what its entries
        already say: the payload's ``nbytes`` (metadata on numpy and
        jax arrays alike), or shape x dtype of this cycle's request
        where a payload carries none. Never converts, fetches or waits
        for a buffer — the count the backend is asked with must cost
        nothing."""
        total = 0
        by_name = None
        for e in entries:
            nbytes = getattr(e.tensor, "nbytes", None)
            if nbytes is None:
                if by_name is None:
                    by_name = {r.tensor_name: r for _, r in bit_requests}
                req = by_name[e.tensor_name]
                nbytes = datatype_size(req.tensor_type)
                for d in req.tensor_shape:
                    nbytes *= int(d)
            total += nbytes
        return total

    def _build_spec_frame(self, hit_mask: int, bit_requests):
        """Build a fused speculative cycle frame: the pure-hit bitmask
        PLUS this rank's pre-packed fused allreduce buffers in
        replay-plan order, or None when the steady set may not be bid
        (_spec_admitted). Entries are only PEEKED: the world may still
        deny the grant, in which case the classic path pops them
        later."""
        admitted = self._spec_admitted(hit_mask, bit_requests)
        if admitted is None:
            return None
        return self._pack_spec_frame(hit_mask, admitted)

    def _spec_admitted(self, hit_mask: int, bit_requests):
        """May this steady set be bid? The replay plan's
        ``[(response, entries)]`` when every batch is speculation-
        eligible, else None (non-allreduce entries in the steady set,
        a data plane of its own — shm/ring/XLA — would carry it, or an
        entry vanished).

        The WHOLE plan is held to every check — the backend's
        fused_cycle_reducible among them, asked with the batch's size
        from metadata (_batch_nbytes) — and no payload is converted:
        a decline costs no device -> host copy and no wait for the
        computation that produces the tensors, and the backend's
        answer is kept per steady set (_spec_declined)."""
        pm = self.parameter_manager
        if pm is not None and self.controller.is_coordinator \
                and pm.plan_revision != self._wire_plan_rev:
            # The tuner just moved the active combo: the pending
            # world-wide eviction must run through _coordinate_cycle
            # this cycle — a native/spec grant would bypass it and
            # keep replaying verdicts of the superseded plan.
            return None
        key = (hit_mask, self._world_fusion_threshold)
        if key in self._spec_declined:
            self._spec_declines += 1
            return None
        plan = self._replay_plan(hit_mask, self._world_fusion_threshold)
        admitted = []
        for resp in plan:
            if resp.response_type != ResponseType.ALLREDUCE:
                return None
            if resp.algorithm not in (_wd.ALG_DEFAULT, _wd.ALG_STAR):
                # Ring/two-level batches own their data plane; the
                # speculative round must not steal them.
                return None
            if resp.wire_dtype == _wd.WIRE_INT8:
                # int8 payloads carry per-rank scales the inline
                # coordinator reduce cannot sum — the classic star
                # path (which dequantizes) keeps carrying them.
                return None
            entries = self.tensor_table.peek_entries(resp.tensor_names)
            if entries is None:
                return None
            try:
                backend = self.op_manager.pick(entries, resp)
            except RuntimeError:
                return None
            if not backend.fused_cycle_reducible(
                    self._batch_nbytes(entries, bit_requests)):
                if len(self._spec_declined) >= 64:
                    self._spec_declined.clear()
                self._spec_declined.add(key)
                self._spec_declines += 1
                return None
            admitted.append((resp, entries))
        return admitted

    def _pack_spec_frame(self, hit_mask: int, admitted):
        """Pack an admitted steady set (_spec_admitted) and count the
        bid. With the zero-copy plane engaged the return value is a
        SteadyPlan (packed into the persistent fusion arena; the cycle
        then runs as one native call), else the serialized frame —
        _negotiate_and_perform dispatches on the type."""
        from horovod_tpu.ops.socket_ops import (
            _pack_fused, _to_numpy, compress_send_payload,
            record_compression,
        )
        inflight = [(resp, entries, [_to_numpy(e.tensor) for e in entries])
                    for resp, entries in admitted]
        if self._steady_native:
            seg_arrays = [arrays for _, _, arrays in inflight]
            seg_wires = [resp.wire_dtype for resp, _ in admitted]
            splan = self._steady_plan_for(hit_mask, seg_arrays,
                                          seg_wires)
            if splan is not None:
                # Coordinator accumulators double as the broadcast
                # result its outputs will alias — fresh, never arena.
                bufs = splan.pack(
                    seg_arrays,
                    [resp.prescale_factor for resp, _ in admitted],
                    use_arena=not self.controller.is_coordinator)
                if any(seg_wires):
                    record_compression(
                        sum(sum(a.nbytes for a in arrays)
                            for arrays in seg_arrays),
                        sum(splan.seg_nbytes))
                self._spec_inflight = inflight
                self._spec_steady = (splan, bufs)
                self._spec_bids += 1
                return splan
        segments = []
        for resp, _, arrays in inflight:
            fused, _ = _pack_fused(arrays, resp)  # applies prescale
            w = resp.wire_dtype
            if w:
                segments.append((_wd.wire_datatype(w),
                                 compress_send_payload(fused, w)))
            else:
                segments.append((numpy_dtype_to_datatype(fused.dtype),
                                 fused))
        self._spec_inflight = inflight
        self._spec_bids += 1
        cache = self._cache
        return self._stamp(wire.serialize_cycle_request(
            CacheCycleRequest(
                epoch=cache.epoch, nslots=cache.nslots,
                hit_mask=hit_mask, spec_payload=segments)))

    def _steady_plan_for(self, hit_mask: int, seg_arrays, seg_wires):
        """Memoized SteadyPlan for (mask, threshold) at the current
        cache epoch; None when a segment's dtype has no native reduce
        kernel (the classic path carries it). With a negotiated wire
        dtype the plan's segments are declared IN the wire dtype — the
        native coordinator reduces bf16/fp16 through the same
        hvd_sum_into codes, and pack compresses into the arena."""
        cache = self._cache
        if self._steady_plan_epoch != cache.epoch:
            self._steady_plans.clear()
            self._steady_plan_epoch = cache.epoch
        key = (hit_mask, self._world_fusion_threshold)
        splan = self._steady_plans.get(key)
        if splan is None:
            segments = []
            for arrays, wire in zip(seg_arrays, seg_wires):
                dtype = arrays[0].dtype
                if any(a.dtype != dtype for a in arrays):
                    return None
                src_nbytes = sum(a.nbytes for a in arrays)
                if wire:
                    np_wire = _wd.wire_np_dtype(wire)
                    count = src_nbytes // dtype.itemsize
                    segments.append((_wd.wire_datatype(wire), np_wire,
                                     count * np_wire.itemsize, dtype))
                else:
                    segments.append((numpy_dtype_to_datatype(dtype),
                                     dtype, src_nbytes, None))
            # In-flight overlap pipelines cycles of DIFFERENT plans:
            # each plan then owns a private arena so the packed send
            # bytes of a submitted cycle can never be overwritten by
            # the next bucket's pack (the runner additionally blocks
            # same-plan resubmission while its views are on the wire).
            arena = (harena.FusionArena() if self._overlap is not None
                     else self._send_arena)
            splan = hsteady.SteadyPlan(
                cache.epoch, cache.nslots, hit_mask, segments, arena,
                chunk_bytes=(0 if self.controller.is_coordinator
                             else self._overlap_chunk),
                world_id=self._world_id)
            if len(self._steady_plans) >= 64:
                self._steady_plans.clear()
            self._steady_plans[key] = splan
        return splan if splan.native_ok else None

    def _native_steady_cycle(self, splan) -> CacheCycleResponse:
        """Drive one zero-copy steady cycle and normalize every
        outcome to the CacheCycleResponse the classic apply path
        consumes. Deviations resume the classic protocol mid-flight:
        the request frame is already on the wire (byte-identical to
        the serialized classic frame), so only the response half
        replays."""
        ctl = self.controller
        _, bufs = self._spec_steady
        self._spec_steady = None
        outcome = ctl.steady_spec_cycle(splan, bufs)
        if outcome is None:
            # Support probe raced (e.g. library refused at call time):
            # run the cycle classically from the serialized frame.
            payload = splan.frame_bytes(bufs)
            gathered = ctl.gather_requests(payload)
            if ctl.is_coordinator:
                reply, meta = self._coordinate_cycle(gathered)
                ctl.broadcast_responses(reply)
            else:
                meta = wire.parse_cycle_response(self._unstamp(
                    ctl.broadcast_responses(None)))
            return meta
        kind, val = outcome
        if kind == "done":
            self._native_steady_cycles += 1
            if ctl.is_coordinator:
                self.timeline.negotiate_cached(fused=True)
                self._check_stall(self._message_table, ctl.size)
            return CacheCycleResponse(
                epoch=splan.epoch, nslots=splan.nslots,
                grant_mask=splan.mask, spec_payload=val)
        if kind == "frame":
            return wire.parse_cycle_response(self._unstamp(val))
        assert kind == "fallback"
        reply, meta = self._coordinate_cycle(val)
        ctl.broadcast_responses(reply)
        return meta

    # -- overlap tier (common/overlap.py) --------------------------------
    def overlap_bucket_plan(self, nbytes_list):
        """Bucket END indices for one grouped submission (ops layer),
        or None when bucketing is off. A pure function of the
        per-tensor sizes plus world-identical knobs/tuned values, so
        every rank splits the same submission the same way."""
        cfg = self.config
        k = cfg.overlap_buckets
        pm = self.parameter_manager
        if pm is not None:
            tuned = pm.overlap_buckets()
            if tuned is not None:
                k = tuned
                if k <= 0:
                    return None
        return hoverlap.plan_buckets(nbytes_list, k,
                                     cfg.overlap_bucket_bytes)

    def note_overlap_buckets(self, n: int) -> None:
        self._overlap_buckets_submitted += n

    def note_bucket_names(self, names) -> None:
        """Record one intended bucket's name set (called by the ops
        layer per bucketed enqueue_group, any thread): the background
        loop splits pops at these boundaries so each bucket
        negotiates as its own cycle. Bounded; snapshot-swapped.
        No-op unless the overlap runner is armed — without it, merged
        pops fusing into one batch is the cheaper outcome."""
        if self._overlap is None:
            return
        s = frozenset(names)
        cur = self._bucket_sets
        if s in cur:
            return
        if len(cur) >= 4 * hoverlap.MAX_BUCKETS:
            cur = frozenset()
        self._bucket_sets = cur | {s}

    def _split_buckets(self, requests: List[Request]) -> List[Request]:
        """Bucketed steady dispatch: when one pop caught SEVERAL
        complete steady buckets back-to-back (the training thread got
        ahead of the wire), peel off the FIRST bucket and requeue the
        rest — each bucket must ride its OWN fused cycle, or the
        union would negotiate as one unknown mask and the per-bucket
        speculation (and the overlap pipeline with it) would unlearn.
        Grouped enqueues are atomic, so pops only ever see whole
        buckets; the requeued remainder is re-popped next iteration
        (which immediately follows — submits count as activity)."""
        if self._overlap is None or len(requests) < 2:
            return requests
        # Only INTENDED bucket sets split pops — never learned steady
        # sets: a per-tensor submission flow (torch-style hooks) may
        # transiently grant a lone tensor, and splitting on that
        # learned singleton would fragment its future fused batches.
        split_sets = self._bucket_sets
        if not split_sets:
            return requests
        seen = set()
        for k, r in enumerate(requests):
            seen.add(r.tensor_name)
            if k + 1 < len(requests) \
                    and frozenset(seen) in split_sets:
                self.tensor_table.requeue(requests[k + 1:])
                if not self._wake.is_set():
                    self._wake.set()
                return requests[:k + 1]
        return requests

    @world_coherent
    def _submit_overlap_cycle(self, splan, bit_requests) -> bool:
        """Hand a packed steady cycle to the overlap runner. Returns
        False (leaving speculative state intact for the synchronous
        path) when the runner cannot accept — a deviation stalled it
        between the loop's drain and this submit, or teardown began.
        @world_coherent: the in-flight mask sequence only ever grows
        here, from a world-identically-built plan in program order."""
        spec = self._spec_steady
        inflight = self._spec_inflight
        self._spec_steady = None
        self._spec_inflight = None
        if spec is None or inflight is None:
            return False
        plan, bufs = spec
        self._overlap_seq += 1
        cyc = hoverlap.InflightCycle(plan, bufs, bit_requests,
                                     inflight, self._overlap_seq)
        try:
            self._overlap.submit(cyc)
        except RuntimeError:
            self._spec_steady = spec
            self._spec_inflight = inflight
            return False
        self._inflight_masks.append(plan.mask)
        if self.timeline.enabled:
            self.timeline.async_start("cycle", "OVERLAP", cyc.seq)
        return True

    def _drain_overlap(self, block: bool = False) -> None:
        """Apply finished overlapped cycles in submission order.
        ``block=True`` waits until NOTHING is outstanding — the wire
        is quiesced and every verdict applied (the precondition for
        any classic round). Runs only on the background thread."""
        runner = self._overlap
        if runner is None:
            return
        while True:
            cyc = runner.pop_completed()
            if cyc is None:
                if not block or not runner.outstanding:
                    return
                t0 = time.monotonic()
                cyc = runner.wait_completed(0.25)
                if cyc is None:
                    continue
                cyc.blocked_wait += time.monotonic() - t0
            self._finish_overlap_cycle(cyc)

    def _finish_overlap_cycle(self, cyc) -> None:
        """Apply one runner outcome — the bg-thread half of an
        overlapped cycle. \"done\" outcomes take the fused-grant fast
        path; anything else resolves through the classic machinery
        after cancelling (and requeueing) every never-sent cycle, so
        the wire order every rank observes stays identical."""
        kind, val = cyc.outcome
        if self.timeline.enabled:
            self.timeline.async_end("cycle", "OVERLAP", cyc.seq)
        if kind == "done":
            self._native_steady_cycles += 1
            self._overlap_cycles += 1
            # The drained cycle IS a completed world round — counted
            # here, at apply time, because verdicts apply in
            # submission order (the wire order every rank shares).
            wc = self._note_round()
            if self._trace_on:
                self._trace.slice(
                    "OVERLAP", cyc.t_start,
                    max(cyc.t_done - cyc.t_start, 0.0), wc)
            if self._metrics_on:
                dur = max(cyc.t_done - cyc.t_start, 1e-9)
                self._m_overlap_fraction.observe(
                    max(0.0, 1.0 - cyc.blocked_wait / dur))
            if self.controller.is_coordinator:
                self.timeline.negotiate_cached(fused=True)
                self._check_stall(self._message_table,
                                  self.controller.size)
            meta = CacheCycleResponse(
                epoch=cyc.plan.epoch, nslots=cyc.plan.nslots,
                grant_mask=cyc.plan.mask, spec_payload=val)
            self._apply_overlap_verdict(cyc, meta)
            return
        # Deviation / error: no later frame was sent (the runner
        # stalls), so cancel the queued cycles and put their requests
        # back — every rank that overlapped does the same at the same
        # verdict, and ranks that never overlapped have them queued
        # anyway; the next cycle re-bids them identically everywhere.
        cancelled = self._overlap.cancel_pending()
        for c in cancelled:
            self._unwind_cancelled_cycle(c)
        if kind == "error":
            err = val
            if isinstance(err, WorldAbortedError):
                entries = [e for (_r, es, _a) in cyc.inflight
                           for e in es]
                popped = self.tensor_table.pop_entries(
                    [e.tensor_name for e in entries]) or entries
                self._drop_inflight_mask(cyc.plan.mask)
                raise self._data_plane_abort(
                    popped, err.origin_rank,
                    getattr(err, "cause", str(err)))
            self._drop_inflight_mask(cyc.plan.mask)
            raise err
        ctl = self.controller
        if kind == "none":
            # Support probe raced: run the cycle classically from the
            # serialized frame (byte-identical to the native send).
            payload = cyc.plan.frame_bytes(cyc.bufs)
            gathered = ctl.gather_requests(payload)
            if ctl.is_coordinator:
                reply, meta = self._coordinate_cycle(gathered)
                ctl.broadcast_responses(reply)
            else:
                meta = wire.parse_cycle_response(self._unstamp(
                    ctl.broadcast_responses(None)))
        elif kind == "frame":
            meta = wire.parse_cycle_response(self._unstamp(val))
        else:
            assert kind == "fallback"
            reply, meta = self._coordinate_cycle(val)
            ctl.broadcast_responses(reply)
        wc = self._note_round()
        if self._trace_on:
            self._trace.slice("OVERLAP", cyc.t_start,
                              max(time.monotonic() - cyc.t_start, 0.0),
                              wc)
        self._apply_overlap_verdict(cyc, meta)

    @world_coherent
    def _apply_overlap_verdict(self, cyc, meta) -> None:
        """Apply a drained cycle's broadcast verdict exactly as the
        synchronous path would: restore ITS speculative in-flight
        state, run the shared cached-cycle apply, and execute whatever
        classic responses the verdict carried."""
        self._spec_inflight = cyc.inflight
        self._drop_inflight_mask(cyc.plan.mask)
        try:
            resp_list = self._apply_cached_cycle(meta,
                                                 cyc.bit_requests)
        finally:
            self._spec_inflight = None
        if self.parameter_manager is not None:
            self.parameter_manager.apply_synced(
                resp_list.tuned_fusion_threshold_bytes,
                resp_list.tuned_cycle_time_ms,
                resp_list.tuned_overlap_buckets)
        self._perform_operations(resp_list)

    @world_coherent
    def _unwind_cancelled_cycle(self, cyc) -> None:
        """A cancelled cycle's frame was never sent: its entries stay
        tabled, its requests go back on the queue (they are cache hits
        and re-bid next cycle), and its mask leaves the in-flight
        sequence — identically on every rank that overlapped."""
        self._drop_inflight_mask(cyc.plan.mask)
        reqs = [req for _slot, req in cyc.bit_requests]
        if reqs:
            self.tensor_table.requeue(reqs)

    @world_coherent
    def _drop_inflight_mask(self, mask: int) -> None:
        try:
            self._inflight_masks.remove(mask)
        except ValueError:
            pass

    def _note_round(self) -> int:
        """One world negotiation round (gather + broadcast — classic,
        cached, native steady or overlapped) completed on this rank.
        The counter is WORLD-IDENTICAL: every rank participates in
        every round in wire order (overlapped cycles apply at drain in
        submission order, which IS the wire order), so the same round
        carries the same number everywhere — the correlation key the
        timeline, the world trace and the flight recorder all stamp."""
        self._world_cycle += 1
        wc = self._world_cycle
        self.timeline.set_world_cycle(wc)
        self._flight.record(htrace.EV_CYCLE, wc)
        htrace.note_cycle(wc)
        return wc

    def _maybe_publish_trace(self) -> None:
        """Per-interval trace shipping (background thread only):
        drain the span collector and either feed rank 0's world
        writer directly or ride one TAG_TRACE frame up the control
        tree — out-of-band, exactly like METRICS frames. The frame
        also carries the clock-sync echo closing the NTP loop."""
        now = time.monotonic()
        # A hierarchical local root forwards buffered child frames on
        # the next tick rather than waiting out its own interval: a
        # child's clock-sync echo ages while parked, and every parked
        # microsecond inflates t4 — a systematic (same-period publish
        # timers, constant phase) negative bias on the leaf's offset
        # that min-RTT filtering cannot remove.
        pending_children = bool(
            getattr(self.controller, "_child_trace", None))
        if (now - self._trace_last_pub < self.config.trace_interval_s
                and not pending_children):
            return
        self._trace_last_pub = now
        spans, dropped = self._trace.drain()
        if self._trace_writer is not None:
            self._trace_writer.add_section(0, spans, dropped)
            self._trace_spans_sent += len(spans)
            return
        echo = htrace.clock().take_echo()
        if (not spans and not dropped and echo is None
                and not pending_children):
            return
        try:
            payload = wire.serialize_trace_frame(
                [{"rank": self.controller.rank, "dropped": dropped,
                  "echo": echo, "spans": spans}])
        except Exception:
            return  # a malformed span must not kill the loop
        self._trace_spans_sent += len(spans)
        self.controller.send_trace(payload)

    def _record_signature(self, req: Request) -> None:
        if req.request_type not in CACHEABLE_REQUESTS:
            return
        numel = 1
        for d in req.tensor_shape[1:]:
            numel *= d
        self._pending_sigs[req.tensor_name] = (
            ResponseCache.signature(req), req.tensor_type, numel)

    def _run_loop_once(self) -> bool:
        """One negotiation cycle; returns False to exit
        (reference: operations.cc:986-1338). With the response cache
        enabled, steady-state cycles ride the bitmask fast path: each
        rank's frame is one bit per cache slot (AND-reduced up the
        gather tree), the coordinator broadcasts the world-granted
        mask, and every rank locally replays the cached responses in
        ascending slot order — no per-tensor serialization, no
        ConstructResponse, no fusion pass. Any miss, signature change,
        eviction, or non-cacheable op rides the full path alongside
        the masks and repopulates the cache coherently everywhere."""
        t0 = time.monotonic()
        self._cycle_count += 1
        faults.tick_cycle(self, self._cycle_count)
        # Demote-verdict pacing: every member EXCEPT the demoted
        # straggler defers a hair (mirroring the delay-fault injection
        # point), so gather arrivals cluster instead of the world
        # blocking inside the collective on one late rank.
        pace = selfop.cycle_pace_s(self.controller.rank)
        if pace > 0.0:
            time.sleep(pace)
        if self._elastic is not None \
                and (t0 - self._selfop_last_tick >= 1.0
                     or selfop.preempted()):
            # Supervision tick: preemption notices on every rank,
            # straggler-demotion analysis on the coordinator. A
            # verdict fans the SAME benign world abort the elastic
            # join sweep uses — the decision is enacted by the next
            # rendezvous barrier. An already-armed preemption event
            # skips the throttle: the grace clock is running, every
            # cycle spent not draining is budget lost.
            self._selfop_last_tick = t0
            decision = self._selfop_policy.tick(self)
            if decision is not None:
                cause, origin = decision
                cause = (f"selfop-{cause}: supervision policy "
                         f"drain-and-resize")
                err = WorldAbortedError(
                    world_abort_message(origin, cause),
                    origin_rank=origin, cause=cause)
                err.resolved = True  # deliberate: drain, then resize
                raise err
        if self._elastic is not None \
                and t0 - self._elastic_last_poll >= 0.25:
            # Elastic join sweep: the coordinator parks any join
            # manifest waiting on its elastic listener and fans a
            # benign world abort so every member reaches the
            # re-rendezvous barrier (where the joiner is admitted);
            # other ranks answer stray dials with a redirect to the
            # current coordinator. Four syscalls a second when idle.
            self._elastic_last_poll = t0
            cause = self._elastic.poll_joins(self.controller.rank == 0)
            if cause is not None:
                err = WorldAbortedError(
                    world_abort_message(-1, cause), origin_rank=-1,
                    cause=cause)
                err.resolved = True  # deliberate: skip the drain
                raise err
        self.timeline.mark_cycle_start()

        if self._overlap is not None and self._overlap.outstanding:
            # Apply finished overlapped cycles (and resolve a parked
            # deviation) BEFORE building this cycle's frame — their
            # verdicts move the cache state the frame build reads.
            self._drain_overlap(block=self._overlap.stalled)

        requests = self.tensor_table.pop_messages()
        if requests:
            # A pass that popped work is one hvd.cycle span, whose
            # wall time is hvd_cycle_seconds; the batch's wait in the
            # queue ends where the span starts.
            queued_ns = self.tensor_table.popped_queued_ns
            with htrace.span("hvd.cycle", n=len(requests),
                             also=self._m_cycle_s) as cyc:
                out = self._negotiate_and_perform(requests)
            if queued_ns and cyc.on:
                htrace.interval("hvd.queue_wait", queued_ns,
                                cyc.start_ns, cyc.cycle, len(requests))
        else:
            out = self._negotiate_and_perform(requests)
        if out is None:
            return True
        resp_list, requests = out
        if resp_list.shutdown:
            return False

        # Pace the cycle (reference: operations.cc:987-995). The autotuner
        # may be steering cycle_time_ms (reference: parameter_manager.cc).
        cycle_time_ms = self.config.cycle_time_ms
        if self._tenant_lane is not None:
            # Report this cycle's negotiated bytes to the tenant
            # scheduler's quota bucket (the live metrics plane carries
            # the same totals; the lane prefers whichever is armed).
            self._tenant_lane.note_cycle(self._cycle_bytes)
            if self.parameter_manager is None:
                self._cycle_bytes = 0
            if self.tensor_table.queue_pending():
                self._tenant_lane.want = True  # backlog persists
        if self.parameter_manager is not None:
            self.parameter_manager.apply_synced(
                resp_list.tuned_fusion_threshold_bytes,
                resp_list.tuned_cycle_time_ms,
                resp_list.tuned_overlap_buckets)
            self.parameter_manager.on_cycle(self._cycle_bytes)
            self._cycle_bytes = 0
            cycle_time_ms = self.parameter_manager.cycle_time_ms()
        if resp_list.responses or requests:
            # Local submissions count as activity too: a rank whose own
            # tensor is still negotiating (peers not yet submitted)
            # must keep cycling at full rate or the blocking gather
            # makes the whole world pay its backoff sleep.
            self._idle_cycles = 0
        else:
            self._idle_cycles += 1
        elapsed = time.monotonic() - t0
        if self._metrics_on:
            self._maybe_publish_metrics()
        if self._trace_on:
            self._maybe_publish_trace()
        idle_hold = False
        sleep_s = cycle_time_ms / 1000.0 - elapsed
        if not self.tensor_table.queue_pending():
            if sleep_s <= 0:
                # The cycle overran the pace budget (normal on a
                # loaded host) and drained everything local. Starting
                # the next world-synchronized round right now loses a
                # race with the completion callbacks' re-enqueue —
                # every steady-state step would pay one DEAD
                # gather+broadcast round of empty frames. Pace from
                # cycle END instead: wait out one cycle period on
                # _wake, which new local work snaps open immediately,
                # so a training loop's next step starts its round with
                # the queue populated. A rank waiting here delays a
                # remote-only negotiation by at most cycle_time_ms —
                # the same bound the reference's start-measured pacing
                # imposes (operations.cc:987-995).
                sleep_s = cycle_time_ms / 1000.0
            if self._steady:
                # Established steady state sharpens that reasoning —
                # and applies even when the cycle FINISHED under
                # budget (fast fused cycles on a quiet host): the
                # world's next round cannot grant ANYTHING until this
                # rank's training thread re-submits a steady set
                # (every collective requires every rank's request), so
                # initiating an empty round early buys nothing and
                # costs everyone a dead gather+broadcast (its
                # AND-grant is zero). Hold until work arrives or a
                # generous deadline passes — the next enqueue and
                # request_shutdown both snap _wake open instantly, and
                # the hold stays far under the heartbeat deadline, so
                # the only cost is bounded frame latency on a world
                # where OTHER ranks are active while this one idles —
                # and their grants were blocked on this rank anyway.
                sleep_s = max(sleep_s, self._bounded_hold_s(
                    8, self._STEADY_IDLE_S, cycle_ms=cycle_time_ms))
                idle_hold = True
        backoff_ms = self.config.idle_backoff_ms
        if backoff_ms > 0 and self._idle_cycles > self._IDLE_GRACE:
            backoff_s = backoff_ms / 1000.0
            if self.config.heartbeat_timeout_s > 0:
                # A sleeping rank sends nothing; its only proof of
                # life is the next cycle's request frame. Cap the
                # backoff under the heartbeat deadline or an idle
                # world's waiting peers would declare the sleeper
                # dead (the two knobs are set independently).
                backoff_s = min(backoff_s,
                                self.config.heartbeat_timeout_s / 2.0)
            ramp = (cycle_time_ms / 1000.0
                    * (self._idle_cycles - self._IDLE_GRACE))
            sleep_s = max(sleep_s, min(backoff_s, ramp))
        # Async checkpoint shards ride the idle/hold windows the pacing
        # machinery already bounds: the submit is a pool handoff, the
        # serialization runs on the checkpoint writer thread while this
        # loop sleeps (common/selfop.py; no-op without
        # HOROVOD_SELFOP_CKPT_DIR).
        selfop.maybe_checkpoint(self.controller.rank,
                                self.controller.size,
                                idle=idle_hold or sleep_s > 0)
        if sleep_s > 0:
            # Wake early on shutdown OR new local work (enqueue sets
            # _wake) so backoff never adds submit latency.
            if idle_hold:
                with htrace.span("hvd.hold", tag="idle") as hold:
                    self._wake.wait(sleep_s)
                self._m_idle_hold_s.inc(
                    (hold.end_ns - hold.start_ns) * 1e-9)
            else:
                self._wake.wait(sleep_s)
        self._wake.clear()
        return True

    def _negotiate_and_perform(self, requests: List[Request]):
        """The work of one pass of the loop on the ``requests`` it
        popped (none on an idle pass): hold for the rest of a burst,
        negotiate one world round, execute what it granted. Returns
        ``(responses, requests)`` for the loop to pace by, or None
        where the pass ends at once (an overlapped cycle was submitted,
        or the overlap regime holds for work)."""
        if requests and self._cache is not None:
            requests = self._absorb_burst(requests)
            requests = self._split_buckets(requests)
        shutting_down = self._shutdown_requested.is_set()

        if self._tenant_lane is not None and requests \
                and not shutting_down:
            # QoS-weighted tenant scheduling (common/tenancy.py): a
            # cycle with local work waits for this tenant's turn in
            # the process-local weighted interleave, and an over-quota
            # tenant is DEFERRED — never skipped, so no frame is ever
            # lost. The wait is bounded by the same hold rule as every
            # other hold in this loop (far under the heartbeat
            # deadline), so a deferred tenant's peers can never
            # mistake pacing for death.
            self._tenant_lane.acquire(self._bounded_hold_s(8, 2.0))

        if (self._overlap is not None and not requests
                and not shutting_down
                and (self._overlap.outstanding or self._steady)):
            # Overlap regime with nothing local to negotiate: hold for
            # work instead of initiating an empty classic round. A
            # wake from a runner completion is NOT work — without this
            # hold, completion wakes leak empty frames into the world
            # rounds, misalign them across ranks, and every
            # speculative bid that lands in such a round dies as a
            # dead grant. Bounded like the steady idle hold (far under
            # the heartbeat deadline) so stall detection, full-path
            # peers and shutdown all keep their liveness: at expiry
            # the empty round proceeds after all.
            now = time.monotonic()
            if self._overlap_hold_deadline is None:
                self._overlap_hold_deadline = now + \
                    self._bounded_hold_s(8, self._STEADY_IDLE_S)
            if now < self._overlap_hold_deadline:
                self._wake.wait(self._overlap_hold_deadline - now)
                self._wake.clear()
                self._drain_overlap(block=False)
                return None
            self._overlap_hold_deadline = None
        elif requests:
            self._overlap_hold_deadline = None

        payload, bit_requests = self._build_request_frame(
            requests, shutting_down)

        # The window hvd_negotiation_seconds times: request gather to
        # response broadcast, whatever path the round takes.
        with htrace.span("hvd.negotiate",
                         also=self._m_negotiation_s) as neg:
            submitted = False
            meta = None
            if not isinstance(payload, hsteady.SteadyPlan) \
                    and self._overlap is not None \
                    and self._overlap.outstanding:
                # Classic frame while cycles are in flight: the wire must
                # quiesce first (cycles are strictly ordered), and the
                # drained verdicts may have moved cache state or requeued
                # cancelled buckets — rebuild the frame afterwards.
                self._drain_overlap(block=True)
                requests.extend(self.tensor_table.pop_messages())
                payload, bit_requests = self._build_request_frame(
                    requests, shutting_down)
            if isinstance(payload, hsteady.SteadyPlan):
                if self._overlap is not None:
                    submitted = self._submit_overlap_cycle(payload,
                                                           bit_requests)
                    if not submitted:
                        # Runner stalled or stopped under us: quiesce, then
                        # run this cycle synchronously — the wire is ours
                        # again once the drain returns. The drain applies
                        # OTHER cycles' verdicts, whose apply path clears
                        # the speculative in-flight state — save THIS
                        # unsent cycle's across it.
                        spec_save = (self._spec_steady,
                                     self._spec_inflight)
                        self._drain_overlap(block=True)
                        self._spec_steady, self._spec_inflight = spec_save
                if not submitted:
                    # Zero-copy steady step: negotiation + data plane in
                    # ONE native call (deviations rejoin the classic path
                    # inside). An abort raised from inside the C loop must
                    # leave no in-flight speculative state behind: elastic
                    # recovery re-enters a fresh cycle loop, and stale
                    # inflight entries would satisfy the next spec verdict
                    # with dead arrays.
                    try:
                        meta = self._native_steady_cycle(payload)
                    except BaseException:
                        self._spec_inflight = None
                        self._spec_steady = None
                        raise
            else:
                gathered = self.controller.gather_requests(payload)
                if self.controller.is_coordinator:
                    reply, meta = self._coordinate_cycle(gathered)
                    self.controller.broadcast_responses(reply)
                else:
                    data = self.controller.broadcast_responses(None)
                    meta = wire.parse_cycle_response(self._unstamp(data))
            if meta is not None:
                # A world round completed synchronously in this iteration
                # (a submitted overlap cycle completes at drain instead).
                self._note_round()
                if neg.on:
                    neg.tag = self._round_kind(payload, meta)

        if submitted:
            # The cycle completes out of band; its verdict applies at
            # a later drain, in submission order. Handles resolve
            # then — synchronize() only ever blocks on the tail
            # bucket. Treat the submit as activity and loop
            # immediately: the next bucket may already be queued.
            self._idle_cycles = 0
            if self._tenant_lane is not None:
                self._tenant_lane.note_cycle(self._cycle_bytes)
                if self.parameter_manager is None:
                    self._cycle_bytes = 0
                if self.tensor_table.queue_pending():
                    self._tenant_lane.want = True  # backlog persists
            if self.parameter_manager is not None:
                self.parameter_manager.on_cycle(self._cycle_bytes)
                self._cycle_bytes = 0
            if self._metrics_on:
                self._maybe_publish_metrics()
            if self._trace_on:
                self._maybe_publish_trace()
            return None

        if isinstance(meta, CacheCycleResponse):
            resp_list = self._apply_cached_cycle(meta, bit_requests)
        else:
            if self._cache is not None:
                raise ConnectionError(
                    "coordinator negotiated without the response cache "
                    "while this rank has it enabled — HOROVOD_CACHE_"
                    "ENABLED/HOROVOD_CACHE_CAPACITY must be identical "
                    "on every rank")
            resp_list = meta

        self._perform_operations(resp_list)
        return resp_list, requests

    @staticmethod
    def _round_kind(payload, meta) -> str:
        """hvd.negotiate's tag: how the round was negotiated."""
        if isinstance(payload, hsteady.SteadyPlan):
            return "steady"
        if isinstance(meta, CacheCycleResponse):
            if meta.spec_payload is not None:
                return "spec"
            if not meta.response_list.responses:
                return "cached"
        return "round"

    def _coordinate_cycle(self, gathered: List[bytes]):
        """Parse every rank's cycle frame and produce this cycle's
        broadcast payload. Returns (payload, meta) where ``meta`` is
        the ResponseList (cache disabled) or CacheCycleResponse that
        every rank — this one included — applies identically."""
        if self._world_id:
            # Tenant world: verify + strip every rank's world-id
            # envelope before parsing (a mismatched id names both
            # worlds instead of decoding a foreign mask).
            gathered = [self._unstamp(f) if f else f for f in gathered]
        cache = self._cache
        if cache is None:
            req_lists = [wire.parse_cycle_request(f)
                         for f in gathered if f]
            for rl in req_lists:
                if not isinstance(rl, RequestList):
                    raise ConnectionError(
                        "a rank negotiated with the response cache "
                        "while the coordinator has it disabled — "
                        "HOROVOD_CACHE_ENABLED/HOROVOD_CACHE_CAPACITY "
                        "must be identical on every rank")
            resp_list = self._coordinate(req_lists)
            return self._stamp(
                wire.serialize_cycle_response(resp_list)), resp_list
        epoch = cache.epoch
        and_hits = -1  # all-ones identity; every rank ANDs one mask in
        or_invalid = 0
        shutdown = False
        req_lists: List[RequestList] = []
        spec_frames: List[CacheCycleRequest] = []
        n_frames = 0
        for f in gathered:
            if not f:
                # member slot folded into its host's CACHED_AGG frame
                continue
            n_frames += 1
            cf = wire.parse_cycle_request(f)
            if not isinstance(cf, CacheCycleRequest):
                raise ConnectionError(
                    "a rank negotiated without the response cache "
                    "while the coordinator has it enabled — "
                    "HOROVOD_CACHE_ENABLED/HOROVOD_CACHE_CAPACITY "
                    "must be identical on every rank")
            if cf.epoch != epoch or cf.nslots != cache.nslots:
                raise ConnectionError(
                    f"response-cache state diverged: a rank reported "
                    f"epoch {cf.epoch}/{cf.nslots} slots vs the "
                    f"coordinator's {epoch}/{cache.nslots} — "
                    f"negotiation cannot continue safely")
            and_hits &= cf.hit_mask
            or_invalid |= cf.invalid_mask
            shutdown = shutdown or cf.shutdown
            if cf.spec_payload is not None:
                spec_frames.append(cf)
            if cf.requests:
                req_lists.append(RequestList(cf.requests, cf.shutdown))
        if self.parameter_manager is not None:
            # Tuner moved the active (algorithm, wire dtype) combo:
            # every cached allreduce verdict was stamped under the
            # OLD plan. Fold a coordinator-initiated eviction of
            # those slots into the broadcast invalid mask — a
            # world-identical event by construction, so every rank's
            # cache (this one included) drops them in the same
            # canonical order and the tensors renegotiate under the
            # new plan. Also suppresses this cycle's spec grant
            # (or_invalid is part of its precondition).
            rev = self.parameter_manager.plan_revision
            if rev != self._wire_plan_rev:
                self._wire_plan_rev = rev
                or_invalid |= self._stale_plan_slots()
        if (spec_frames and len(spec_frames) == n_frames
                and not shutdown and not or_invalid
                and all(cf.hit_mask == and_hits
                        for cf in spec_frames)):
            # Fused speculative cycle: every rank bid the SAME pure-hit
            # mask with its fused buffers attached — reduce inline and
            # broadcast grant + result in this very response. One
            # world round-trip total: no separate data-plane round, no
            # ConstructResponse, no fusion pass.
            reduced = self._reduce_spec(spec_frames)
            self.timeline.negotiate_cached(fused=True)
            # Stall detection must not go blind while the world hums
            # in fused steady state: a full-path tensor some rank
            # submitted earlier may still be aging in the table.
            self._check_stall(self._message_table,
                              self.controller.size)
            meta = CacheCycleResponse(epoch=epoch,
                                      nslots=cache.nslots,
                                      grant_mask=and_hits,
                                      spec_payload=reduced)
            return self._stamp(wire.serialize_cycle_response(meta)), \
                meta
        grant = and_hits & ~or_invalid
        resp_list = self._coordinate(req_lists,
                                     extra_shutdown=shutdown)
        if grant and not resp_list.responses:
            self.timeline.negotiate_cached()
        meta = CacheCycleResponse(epoch=epoch, nslots=cache.nslots,
                                  grant_mask=grant,
                                  invalid_mask=or_invalid,
                                  response_list=resp_list)
        return self._stamp(wire.serialize_cycle_response(meta)), meta

    def _stale_plan_slots(self) -> int:
        """Mask of every cached slot holding an ALLREDUCE verdict —
        the entries whose stamped (algorithm, wire dtype) belongs to
        a superseded tuner plan. Read-only over the coordinator's own
        cache; the eviction itself happens on every rank through the
        broadcast invalid mask."""
        return self._cache.slot_mask(ResponseType.ALLREDUCE)

    # Canonical ascending-bit iteration, shared with the cache's own
    # mask-driven mutations (coordinator.iter_set_bits) so replay and
    # eviction can never drift apart.
    _iter_slots = staticmethod(iter_set_bits)

    @world_coherent
    def _apply_cached_cycle(self, meta: CacheCycleResponse,
                            bit_requests: List[tuple]) -> ResponseList:
        """Apply the coordinator's cycle verdict to the local cache —
        identically on every rank: evict the OR'ed invalid slots
        (ascending), replay the granted slots (ascending, fused with
        the threshold this very frame carries), repopulate from the
        freshly negotiated responses (stream order), and requeue hits
        the world did not grant. @world_coherent: every input here is
        the broadcast verdict itself."""
        cache = self._cache
        if cache is None or meta.epoch != cache.epoch \
                or meta.nslots != cache.nslots:
            local = ("disabled" if cache is None
                     else f"epoch {cache.epoch}/{cache.nslots} slots")
            raise ConnectionError(
                f"response-cache state diverged from the coordinator "
                f"(local {local}, coordinator epoch "
                f"{meta.epoch}/{meta.nslots} slots) — negotiation "
                f"cannot continue safely")
        if meta.spec_payload is not None:
            return self._complete_spec_cycle(meta, bit_requests)
        inner = meta.response_list
        if meta.invalid_mask:
            cache.evict_slots(meta.invalid_mask)
        if inner.tuned_fusion_threshold_bytes:
            # The coordinator's effective threshold — the WORLD value
            # every rank must replay and speculate with.
            self._world_fusion_threshold = \
                inner.tuned_fusion_threshold_bytes
        replayed: List[Response] = []
        if meta.grant_mask:
            replayed = self._replay_grants(meta.grant_mask,
                                           self._world_fusion_threshold)
            if not inner.responses:
                self._cached_cycles += 1
        if inner.responses:
            self._populate_cache(inner)
        if bit_requests and not inner.shutdown:
            now = time.monotonic()
            missed = []
            for slot, req in bit_requests:
                if (meta.grant_mask >> slot) & 1:
                    self._bit_pending_since.pop(req.tensor_name, None)
                else:
                    self._bit_pending_since.setdefault(
                        req.tensor_name, now)
                    missed.append(req)
            self._requeued_names = frozenset(
                r.tensor_name for r in missed)
            if missed:
                self.tensor_table.requeue(missed)
            # A fully granted pure-hit cycle makes its mask (and name
            # set) a steady-state prediction: _absorb_burst holds for
            # its enqueue bursts, and the next identical cycle may
            # speculate its fused payload onto the bitmask round.
            if self._steady_epoch != cache.epoch:
                # slot<->name bindings moved; every mask is stale
                self._steady.clear()
                self._spec_denied.clear()
                self._spec_declined.clear()
                self._steady_epoch = cache.epoch
            if self._spec_inflight is not None and not missed:
                # We bid speculatively; the world granted everything
                # yet answered classically — some peer will not (or
                # cannot) speculate. Count it so repeat bids stop
                # wasting a full fused payload per cycle.
                bid = 0
                for slot, _req in bit_requests:
                    bid |= 1 << slot
                self._spec_denied[bid] = \
                    self._spec_denied.get(bid, 0) + 1
                self._spec_denials_total += 1
                self._spec_inflight = None
            if not missed and not inner.responses \
                    and not meta.invalid_mask:
                self._steady[meta.grant_mask] = frozenset(
                    cache.entry(s).name
                    for s in self._iter_slots(meta.grant_mask))
                self._steady.move_to_end(meta.grant_mask)
                if len(self._steady) > self._steady_cap:
                    self._steady.popitem(last=False)
            elif meta.grant_mask or inner.responses \
                    or meta.invalid_mask:
                # a PARTIAL verdict for this bid: whatever mask was
                # bid is not unanimously steady — drop it so repeat
                # bids stop wasting speculative payloads. A fully
                # DENIED bid (dead round: some rank simply had
                # nothing queued yet, a scheduling race) keeps its
                # prediction and re-speculates on the re-bid.
                bid_mask = 0
                for slot, _req in bit_requests:
                    bid_mask |= 1 << slot
                self._steady.pop(bid_mask, None)
        if not replayed:
            return inner
        return ResponseList(
            replayed + inner.responses, shutdown=inner.shutdown,
            tuned_cycle_time_ms=inner.tuned_cycle_time_ms,
            tuned_fusion_threshold_bytes=(
                inner.tuned_fusion_threshold_bytes))

    def _replay_plan(self, grant_mask: int,
                     threshold: int) -> List[Response]:
        """The fused execution list for a granted mask: clone the
        granted entries in ascending slot order and fuse them exactly
        as the coordinator would have. Memoized per (grant, threshold)
        for the current cache epoch — a steady-state training loop
        grants the same mask every cycle, so this collapses to a dict
        hit. Pure: never touches the LRU (the speculative frame
        builder calls it before any grant exists)."""
        cache = self._cache
        if self._replay_epoch != cache.epoch:
            self._replay_plans.clear()
            self._replay_epoch = cache.epoch
        key = (grant_mask, threshold)
        plan = self._replay_plans.get(key)
        if plan is None:
            responses: List[Response] = []
            dtypes: Dict[str, DataType] = {}
            slices: Dict[str, int] = {}
            for slot in self._iter_slots(grant_mask):
                e = cache.entry(slot)
                responses.append(e.clone_response())
                dtypes[e.name] = e.dtype
                slices[e.name] = e.slice_numel
            plan = fuse_responses(responses, dtypes, threshold, slices)
            if len(self._replay_plans) >= 64:
                self._replay_plans.clear()
            self._replay_plans[key] = plan
        return plan

    def _replay_grants(self, grant_mask: int,
                       threshold: int) -> List[Response]:
        plan = self._replay_plan(grant_mask, threshold)
        self._cache.touch_mask(grant_mask)
        return plan

    @staticmethod
    def _reduce_spec(spec_frames: List[CacheCycleRequest]):
        """Coordinator half of the fused speculative cycle: sum every
        rank's pre-packed fused buffers segment-by-segment (ascending
        rank order, mirroring the star data plane). Frames already
        passed the epoch/mask equality gate, so a layout mismatch here
        means the caches diverged structurally — fail fast."""
        import numpy as np

        from horovod_tpu import native as _native
        first = spec_frames[0].spec_payload
        if any(len(sf.spec_payload) != len(first)
               for sf in spec_frames[1:]):
            raise ConnectionError(
                "speculative fused payloads disagree on layout "
                "across ranks — response-cache state diverged")
        out = []
        for i, (dt, buf0) in enumerate(first):
            np_dt = datatype_to_numpy_dtype(dt)
            acc = np.frombuffer(buf0, dtype=np_dt).copy()
            for sf in spec_frames[1:]:
                d2, b2 = sf.spec_payload[i]
                if d2 != dt or b2.nbytes != buf0.nbytes:
                    raise ConnectionError(
                        "speculative fused payloads disagree on "
                        "layout across ranks — response-cache state "
                        "diverged")
                src = np.frombuffer(b2, dtype=np_dt)
                if not _native.sum_into(acc, src):
                    acc += src
            out.append((dt, acc))
        return out

    @world_coherent
    def _complete_spec_cycle(self, meta: CacheCycleResponse,
                             bit_requests: List[tuple]) -> ResponseList:
        """Worker half of the fused speculative cycle: the grant is by
        construction exactly what this rank bid, and the payload is
        the world-reduced result of the buffers it packed at frame
        build — unpack into the (still-tabled) entries, fire their
        callbacks, and keep every counter/LRU effect identical to a
        classic hit cycle so cache coherence is unaffected."""
        from horovod_tpu.ops.socket_ops import _unpack_fused
        import numpy as np
        inflight = self._spec_inflight
        self._spec_inflight = None
        if inflight is None or meta.spec_payload is None \
                or len(meta.spec_payload) != len(inflight):
            raise ConnectionError(
                "fused speculative response does not match the frame "
                "this rank sent — control plane corrupted")
        timeline_on = self.timeline.enabled
        metrics_on = self._metrics_on
        ok = Status.OK()
        for (resp, entries, arrays), (dt, buf) in zip(
                inflight, meta.spec_payload):
            self._op_count += 1
            faults.tick_op(self, self._op_count)
            if metrics_on:
                # The fused round IS the data plane for this batch:
                # keep the allreduce op/byte totals exact even though
                # OperationManager.execute never sees it.
                self._m_ops_allreduce.inc()
                self._m_bytes_allreduced.inc(
                    sum(a.nbytes for a in arrays))
            # Autotune score attribution: spec cycles bypass
            # _perform_operations, so their bytes must feed the
            # tuner's bytes/µs stream here (the grid phase measures
            # the deployment regime, spec cycle included).
            self._cycle_bytes += sum(a.nbytes for a in arrays)
            names = resp.tensor_names
            popped = self.tensor_table.pop_entries(names)
            if resp.wire_dtype:
                # Compressed steady cycle: the world result arrived in
                # the negotiated wire dtype; decompress ONCE into a
                # fresh full-precision array outputs may alias (a
                # cast, not a fallback byte copy — hvd_data_copies
                # stays 0 on this path).
                result = _wd.decompress(
                    buf, resp.wire_dtype, arrays[0].dtype,
                    sum(a.size for a in arrays))
            elif isinstance(buf, np.ndarray):
                # Zero-copy plane: the native cycle received the world
                # result into a FRESH writable per-step buffer (never
                # arena memory), so outputs may alias it directly.
                result = buf
            else:
                # Classic frame: a memoryview over the immutable recv
                # bytes — one defensive copy buys writable outputs
                # (the contract of the star plane), and the counter
                # records that the fallback path is carrying traffic.
                self._m_data_copies.inc()
                result = np.frombuffer(bytearray(buf),
                                       dtype=datatype_to_numpy_dtype(dt))
            op_name = resp.response_type.name
            if timeline_on:
                for n in names:
                    self.timeline.start(n, op_name)
            _unpack_fused(entries, arrays, result, resp)
            if timeline_on:
                for n in names:
                    self.timeline.end(n)
            for e in popped:
                if e.callback:
                    e.callback(ok)
        self._cached_cycles += 1
        self._spec_cycles += 1
        self._spec_denied.pop(meta.grant_mask, None)
        self._cache.touch_mask(meta.grant_mask)
        for _slot, req in bit_requests:
            self._bit_pending_since.pop(req.tensor_name, None)
        self._requeued_names = frozenset()
        return ResponseList([])

    @staticmethod
    def _unfuse(resp: Response, i: int, world_size: int) -> Response:
        """Entry ``i`` of a (possibly fused) response as a standalone
        single-tensor Response — the unit the cache stores, so a later
        hit cycle can re-fuse under whatever threshold is then in
        effect. ALLGATHER tensor_sizes are entry-major
        (sizes[ec * world_size + rc]); ALLREDUCE sizes are per-entry
        numels; every other cacheable type never fuses."""
        if resp.response_type == ResponseType.ALLGATHER:
            sizes = list(resp.tensor_sizes[i * world_size:
                                           (i + 1) * world_size])
        elif resp.tensor_sizes:
            sizes = [resp.tensor_sizes[i]]
        else:
            sizes = []
        return Response(response_type=resp.response_type,
                        tensor_names=[resp.tensor_names[i]],
                        devices=list(resp.devices),
                        tensor_sizes=sizes,
                        prescale_factor=resp.prescale_factor,
                        postscale_factor=resp.postscale_factor,
                        wire_dtype=resp.wire_dtype,
                        algorithm=resp.algorithm)

    @world_coherent
    def _populate_cache(self, resp_list: ResponseList) -> None:
        """Refresh the cache from freshly negotiated responses — in
        broadcast-stream order, the world-identical order every rank
        sees, which is what keeps slot assignment and LRU eviction
        bit-identical everywhere. ERROR verdicts evict any stale entry
        under the same names."""
        cache = self._cache
        world_size = self.controller.size
        for resp in resp_list.responses:
            rt = resp.response_type
            if rt == ResponseType.ERROR:
                for name in resp.tensor_names:
                    cache.evict_name(name)
                    self._pending_sigs.pop(name, None)
                continue
            if rt not in CACHEABLE_RESPONSES:
                for name in resp.tensor_names:
                    self._pending_sigs.pop(name, None)
                continue
            for i, name in enumerate(resp.tensor_names):
                info = self._pending_sigs.pop(name, None)
                if info is None:
                    # A response for a tensor this rank never submitted
                    # through the full path: the negotiation streams
                    # have diverged; continuing would silently diverge
                    # the cache next.
                    raise ConnectionError(
                        f"negotiated response for tensor {name!r} "
                        f"without a matching local request — control "
                        f"plane corrupted")
                sig, dtype, slice_numel = info
                cache.put(name, sig, self._unfuse(resp, i, world_size),
                          dtype, slice_numel)

    # -- metrics plane ---------------------------------------------------
    def _collect_runtime_metrics(self) -> None:
        """Registry collector: mirror counters whose true source lives
        on hot paths that must not pay per-event metric calls (cache
        hit/miss tallies, cycle counts, queue depth, per-peer
        heartbeat ages). Runs once per snapshot, never per event."""
        c = self._cache
        if c is not None:
            self._m_cache_hits.set_total(c.hits)
            self._m_cache_misses.set_total(c.misses)
            self._m_cache_evictions.set_total(c.evictions)
            self._m_cache_entries.set(len(c))
        self._m_world_size.set(self.controller.size)
        if self._elastic is not None:
            self._m_world_resizes.set_total(self._elastic.resizes)
            self._m_elastic_rejoins.set_total(
                self._elastic.rejoins_admitted)
            for v in self._elastic.take_rendezvous_observations():
                self._m_rdzv_s.observe(v)
            self._m_sync_bytes.set_total(
                self._elastic.sync_bytes_total)
            for dt_s, _ in self._elastic.take_sync_observations():
                self._m_sync_s.observe(dt_s)
        # Supervision decisions mirror lazily per kind — the series
        # appears the first time the policy makes that decision.
        for kind, n in selfop.decision_counts().items():
            m = self._selfop_decision_metrics.get(kind)
            if m is None:
                m = self.metrics.counter(
                    f'hvd_supervisor_decisions_total{{kind="{kind}"}}',
                    "supervision-policy decisions this process made "
                    "(common/selfop.py)")
                self._selfop_decision_metrics[kind] = m
            m.set_total(n)
        self._m_ckpt_age.set(selfop.checkpoint_age_s())
        self._m_cycles.set_total(self._cycle_count)
        self._m_cached_cycles.set_total(self._cached_cycles)
        self._m_spec_cycles.set_total(self._spec_cycles)
        self._m_spec_bids.set_total(self._spec_bids)
        self._m_spec_declines.set_total(self._spec_declines)
        self._m_spec_denials.set_total(self._spec_denials_total)
        self._m_native_steady.set_total(self._native_steady_cycles)
        self._m_overlap_cycles.set_total(self._overlap_cycles)
        self._m_overlap_buckets.set_total(
            self._overlap_buckets_submitted)
        self._m_inflight.set(
            self._overlap.outstanding if self._overlap is not None
            else 0)
        self._m_arena_bytes.set(harena.total_bytes())
        self._m_queue_depth.set(len(self.tensor_table))
        self._m_lock_inversions.set_total(lockdep.inversion_count())
        self._m_affinity_violations.set_total(
            threadcheck.violation_count())
        self._m_trace_spans.set_total(self._trace_spans_sent)
        for r, age in self.controller.peer_heartbeat_ages().items():
            self.metrics.gauge(
                f'hvd_peer_heartbeat_age_seconds{{peer="{r}"}}',
                "seconds since the last control frame from this peer",
                agg=hmetrics.AGG_MAX).set(age)

    def _maybe_publish_metrics(self) -> None:
        """Per-interval fold point (background thread only): snapshot
        the local registry, then either feed the rank-0 aggregator
        (plus the JSONL log) or ship one compact METRICS frame up the
        control tree — out-of-band, the way PING frames ride."""
        now = time.monotonic()
        if now - self._metrics_last_pub \
                < self.config.metrics_interval_s:
            return
        self._metrics_last_pub = now
        snap = self.metrics.snapshot()
        if self._aggregator is not None:
            self._aggregator.update_local(snap)
            if self._metrics_log is not None:
                self._metrics_log.append(self._aggregator.world())
            return
        try:
            payload = wire.serialize_metrics_frame(1, snap)
        except Exception:
            return  # a malformed record must not kill the loop
        self.controller.send_metrics(payload)

    def metrics_view(self) -> Dict:
        """The horovod_tpu.metrics() payload: the freshest local
        snapshot, the world aggregate (rank 0; None elsewhere — the
        world view materializes only at the fold point), and the HTTP
        port when the Prometheus endpoint is live."""
        local = self.metrics.snapshot()
        view = {"enabled": self._metrics_on, "local": local,
                "world": None, "http_port": None}
        if self._aggregator is not None:
            self._aggregator.update_local(local)
            world = self._aggregator.world()
            if not self._world_id:
                # The fleet's read surface also carries its co-located
                # tenants' world folds: every tenant series is
                # tenant-labelled, so the merge is collision-free (a
                # tenant whose coordinator lives elsewhere appears on
                # THAT process's surface instead).
                world = _merge_tenant_worlds(world)
            view["world"] = world
        if self._metrics_http is not None:
            view["http_port"] = self._metrics_http.port
        return view

    def _world_status_line(self) -> str:
        """Steady-state health context for the stall report: queue
        depth and timeline drops always; per-peer heartbeat ages when
        the metrics plane maintains them — one warning then carries
        enough to diagnose without a second tool."""
        parts = [f"world cycle {self._world_cycle}",
                 f"tensor queue depth {len(self.tensor_table)}"]
        if self._world_id:
            # Per-tenant line: which job this runtime serves, and how
            # the process-local scheduler has been treating it — a
            # starved tenant's stall warning answers "why" inline.
            line = (f"tenant {self._tenant or '?'} "
                    f"(world {self._world_id:#010x})")
            lane = self._tenant_lane
            if lane is not None:
                line += ": " + lane.status_line()
            parts.append(line)
        if self._last_wire_verdict is not None:
            alg, w = self._last_wire_verdict
            line = (f"wire plan {_wd.ALG_NAMES.get(alg, alg)}"
                    f"/{_wd.WIRE_NAMES.get(w, w)}")
            parts.append(line)
        if self._elastic is not None:
            parts.append(self._elastic.world_line())
        selfop_line = self._selfop_policy.status_line()
        if selfop_line:
            parts.append(selfop_line)
        ages = self.controller.peer_heartbeat_ages()
        if ages:
            # Ages are last-frame-to-now durations measured on THIS
            # host's clock — on rank 0 (where the stall report runs)
            # that IS the coordinator clock, and the offsets line
            # below quantifies how far each peer's own clock sits
            # from it, so a skewed host's timeline no longer reads
            # as "silent".
            worst = sorted(ages.items(), key=lambda kv: -kv[1])[:4]
            parts.append(
                "oldest peer heartbeat ages (coordinator clock): "
                + ", ".join(f"rank {r} {a:.1f}s" for r, a in worst))
        if self.controller.is_coordinator:
            offs = htrace.clock_offsets_line()
            if offs:
                parts.append("peer clock offsets vs coordinator: "
                             + offs)
        if self.timeline.dropped_events:
            parts.append(f"timeline events dropped "
                         f"{self.timeline.dropped_events}")
        return "; ".join(parts)

    def negotiation_cache_stats(self) -> Dict:
        """Local observability for benchmarks, tests and the stall
        report: lookup hit/miss counters, cached-cycle count, and the
        coherent-state epoch."""
        c = self._cache
        if c is None:
            return {"enabled": False}
        total = c.hits + c.misses
        return {"enabled": True, "capacity": c.capacity,
                "entries": len(c), "hits": c.hits, "misses": c.misses,
                "hit_rate": (c.hits / total) if total else 0.0,
                "cached_cycles": self._cached_cycles,
                "spec_cycles": self._spec_cycles,
                "spec_bids": self._spec_bids,
                "spec_declines": self._spec_declines,
                "native_steady_cycles": self._native_steady_cycles,
                "overlap_cycles": self._overlap_cycles,
                "overlap_inflight": (self._overlap.outstanding
                                     if self._overlap is not None
                                     else 0),
                "epoch": c.epoch}

    def _cache_stats_line(self) -> str:
        s = self.negotiation_cache_stats()
        if not s.get("enabled"):
            return ""
        return (f"cache: {s['hits']} hits / {s['misses']} misses "
                f"({s['hit_rate']:.1%} hit rate), "
                f"{s['cached_cycles']} fully cached cycles "
                f"({s['spec_cycles']} fused single-round, "
                f"{s['native_steady_cycles']} native zero-copy, "
                f"{s['overlap_cycles']} overlapped), "
                f"{s['entries']}/{s['capacity']} slots")

    def _check_stall(self, table: MessageTable, size: int) -> None:
        """Periodic coordinator-side stall scan — runs on EVERY cycle
        shape, including fused speculative ones (a tensor one rank
        submitted the full way can sit in the MessageTable while the
        rest of the world hums along in fused steady state; the PR 2
        stall warnings and fail-fast shutdown must still see it)."""
        if not self._stall.should_check():
            return
        straggler = (self._straggler.report_line()
                     if self._straggler is not None else "")
        if self._stall.check(table,
                             cache_stats=self._cache_stats_line(),
                             world_stats=self._world_status_line(),
                             straggler_stats=straggler):
            self._flight.record(htrace.EV_STALL, self._world_cycle,
                                note="stall shutdown threshold")
            # The stall-shutdown threshold fires the fail-fast
            # abort so every rank gets a structured error naming
            # the condition, instead of the silent clean-shutdown
            # fan-out the reference performs (operations.cc:609).
            # Blame the stalled rank(s), not the healthy
            # coordinator observing them: the missing ranks on the
            # OLDEST pending tensor are the culprits. origin -1
            # ("unknown rank") only if the table emptied racily.
            origin, missing_note = -1, ""
            pending = sorted(table.pending(), key=lambda p: -p[1])
            if pending:
                name, _, reported = pending[0]
                missing = [r for r in range(size)
                           if r not in set(reported)]
                if missing:
                    origin = min(missing)
                    missing_note = (f" (tensor '{name}' never "
                                    f"submitted by ranks "
                                    f"{missing})")
            cause = ("stall shutdown threshold "
                     f"({self._stall.shutdown_time:g}s) exceeded: "
                     "one or more tensors were never submitted by "
                     "every rank (see coordinator stall warnings "
                     f"for names and missing ranks){missing_note}")
            raise WorldAbortedError(world_abort_message(origin,
                                                        cause),
                                    origin_rank=origin, cause=cause)

    def _stamp_wire_plan(self, fused: List[Response]) -> None:
        """Coordinator-side algorithm/dtype stamping of a cycle's
        fused allreduce batches: the policy (static config or the
        autotuner's per-bucket table) picks the ALG_* route for the
        batch's UNCOMPRESSED size and may cap the min-resolved wire
        dtype (the tuner explores dtypes by capping — it can only
        ever weaken a rank's proposal, never exceed it, so tuning
        stays numerics-safe). Runs before the broadcast, so the
        verdicts ride the same world-identical response stream as
        everything else."""
        for resp in fused:
            if resp.response_type != ResponseType.ALLREDUCE \
                    or not resp.tensor_names:
                continue
            dtype = self._dtypes.get(resp.tensor_names[0])
            if dtype is None:
                continue
            nbytes = sum(resp.tensor_sizes) * datatype_size(dtype)
            alg, cap = self._wire_policy.plan(nbytes)
            resp.algorithm = alg
            if cap is not None and resp.wire_dtype > cap:
                resp.wire_dtype = cap
            if alg or resp.wire_dtype:
                self._last_wire_verdict = (alg, resp.wire_dtype)
                self.timeline.wire_plan(
                    f"{_wd.ALG_NAMES[alg]}/"
                    f"{_wd.WIRE_NAMES[resp.wire_dtype]}")

    def _coordinate(self, req_lists: List[RequestList],
                    extra_shutdown: bool = False) -> ResponseList:
        """Coordinator half of the cycle
        (reference: operations.cc:1018-1258)."""
        table = self._message_table
        size = self.controller.size
        shutdown = extra_shutdown
        for rl in req_lists:
            shutdown = shutdown or rl.shutdown
            for req in rl.requests:
                self._dtypes[req.tensor_name] = req.tensor_type
                numel = 1
                for d in req.tensor_shape[1:]:
                    numel *= d
                self._slice_numels[req.tensor_name] = numel
                table.increment_tensor_count(req, size, self.timeline)
        ready = table.pop_ready()
        responses = []
        for name in ready:
            resp = construct_response(table, name, size)
            # The NEGOTIATE_* span's end names the resolved wire
            # dtype, so a timeline reader can see compression engage
            # per tensor without cross-referencing metrics.
            self.timeline.negotiate_end(
                name, verdict=_wd.WIRE_NAMES[resp.wire_dtype]
                if resp.wire_dtype else "")
            responses.append(resp)
        threshold = self.config.fusion_threshold_bytes
        if self.parameter_manager is not None:
            threshold = self.parameter_manager.fusion_threshold_bytes()
        fused = fuse_responses(responses, self._dtypes, threshold,
                               self._slice_numels)
        self._stamp_wire_plan(fused)
        for resp in fused:
            for n in resp.tensor_names:
                self._dtypes.pop(n, None)
                self._slice_numels.pop(n, None)

        self._check_stall(table, size)

        resp_list = ResponseList(fused, shutdown=shutdown)
        if self.parameter_manager is not None:
            resp_list.tuned_cycle_time_ms = \
                self.parameter_manager.cycle_time_ms()
            resp_list.tuned_fusion_threshold_bytes = \
                self.parameter_manager.fusion_threshold_bytes()
            resp_list.tuned_overlap_buckets = \
                self.parameter_manager.tuned_overlap_buckets
        elif self._cache is not None:
            # Cached-cycle replay re-fuses granted slots on every rank
            # with this threshold; broadcast the coordinator's value
            # so a rank launched with a divergent
            # HOROVOD_FUSION_THRESHOLD converges instead of building
            # mismatched fused batches from the same grant.
            resp_list.tuned_fusion_threshold_bytes = \
                self.config.fusion_threshold_bytes
        return resp_list

    class _SpanCloser:
        """Closes a fused batch's timeline COLLECTIVE + top-level spans
        exactly once, when the LAST entry's completion callback fires —
        so async (InProgress) collectives trace their true duration
        instead of their issue time, the way the reference's CUDA
        finalizer thread drives Timeline end
        (reference: cuda_operations.cc:148-179). The deferred spans are
        Chrome ASYNC NESTABLE events keyed by a per-batch id: a tensor
        may legally re-negotiate the same name while its previous batch
        is still in flight, and deferred plain B/E events would mispair
        on the per-pid stack. Thread-safe: async callbacks arrive from
        finalizer threads; the timeline is a queue fed from any
        thread."""

        __slots__ = ("_timeline", "_names", "_op_name", "_batch_id",
                     "_remaining", "_lock", "_closed")

        def __init__(self, timeline, names, op_name: str,
                     batch_id: int, n_entries: int):
            self._timeline = timeline
            self._names = names
            self._op_name = op_name
            self._batch_id = batch_id
            self._remaining = n_entries
            self._lock = lockdep.lock("runtime._SpanCloser._lock")
            self._closed = False

        def entry_done(self) -> None:
            with self._lock:
                self._remaining -= 1
                if self._remaining > 0 or self._closed:
                    return
                self._closed = True
            self._close()

        def _close(self) -> None:
            for n in self._names:
                self._timeline.async_end(n, ACT_COLLECTIVE,
                                         self._batch_id)
            for n in self._names:
                self._timeline.async_end(n, self._op_name,
                                         self._batch_id)

    def _perform_operations(self, resp_list: ResponseList) -> None:
        """Execute each agreed response and fire callbacks
        (reference: operations.cc:450-539 PerformOperation)."""
        for response in resp_list.responses:
            self._op_count += 1
            faults.tick_op(self, self._op_count)
            if response.wire_dtype or response.algorithm:
                # Rank-local observability: the stall report names the
                # last applied (algorithm, wire dtype) on every rank,
                # not just the stamping coordinator.
                self._last_wire_verdict = (response.algorithm,
                                           response.wire_dtype)
            entries = self.tensor_table.pop_entries(
                response.tensor_names)
            if response.response_type == ResponseType.ERROR:
                for e in entries:
                    if e.callback:
                        e.callback(
                            Status.PreconditionError(response.error_message))
                continue
            if not entries and response.response_type != ResponseType.BARRIER:
                continue
            names = [e.tensor_name for e in entries]
            op_name = response.response_type.name
            # Async-capable batches trace through async-nestable span
            # events closed at COMPLETION by _SpanCloser; everything
            # else keeps the reference's plain B/E spans.
            use_async_spans = (self.finalizer is not None
                               and self.timeline.enabled
                               and bool(entries))
            closer = None
            if use_async_spans:
                self._batch_seq += 1
                closer = self._SpanCloser(self.timeline, names, op_name,
                                          self._batch_seq, len(entries))
                for n in names:
                    self.timeline.async_start(n, op_name,
                                              self._batch_seq)
            elif self.timeline.enabled:
                for e in entries:
                    self.timeline.start(e.tensor_name, op_name)
            # Input readiness: the reference polls CUDA ReadyEvents here
            # (operations.cc:507-518) because its backends consume raw
            # device pointers. JAX tensors are futures — every consumer
            # (np.asarray on the socket path, device_put/jit on the mesh
            # path) orders on the producing computation, so a blocking
            # poll adds nothing but latency (and is_ready() from a
            # non-main thread costs ~100 ms flat on some platforms).
            # The QUEUE activity stays in the trace as the handoff
            # marker between negotiation and execution.
            self.timeline.activity_start_all(names, ACT_QUEUE)
            self.timeline.activity_end_all(names)

            # Async backends fire entry callbacks from finalizer threads
            # when the collective COMPLETES; pre-wrap them so the batch's
            # timeline spans close at that true end (sync backends fire
            # the same wrappers in-loop below — same path, same result).
            if use_async_spans:
                for n in names:
                    self.timeline.async_start(n, ACT_COLLECTIVE,
                                              self._batch_seq)
                for e in entries:
                    user_cb = e.callback

                    def _cb(status, _u=user_cb, _c=closer):
                        _c.entry_done()
                        if _u:
                            _u(status)

                    e.callback = _cb
            else:
                self.timeline.activity_start_all(names, ACT_COLLECTIVE)
            # The batch's hvd.execute span is opened in the manager,
            # which knows the backend (issue-side wall time: an async
            # backend completes on a finalizer thread).
            self.exec_cycle = self._world_cycle
            try:
                status = self.op_manager.execute(entries, response)
            except WorldAbortedError as e:
                # An abort notice surfaced mid-collective (e.g. the
                # controller channel died during a data-plane
                # gather): fail this batch with the structured status,
                # then let the loop-level handler fan the abort. The
                # origin is resolved against any queued control-plane
                # notice BEFORE the callbacks fire — these complete
                # user-visible handles, and a data-plane blame can
                # misattribute a cascading teardown (see _fail_world).
                raise self._data_plane_abort(
                    entries, e.origin_rank,
                    getattr(e, "cause", str(e))) from e
            except (ConnectionError, OSError, TimeoutError) as e:
                # Data-plane transport failure (dead ring neighbor,
                # severed link): this is a world-level event, not a
                # per-batch soft error — a lone UnknownError here
                # would leave every peer blocked mid-collective.
                rank = self.controller.rank
                raise self._data_plane_abort(
                    entries, rank,
                    f"data-plane failure during {op_name} on "
                    f"rank {rank}: {e}") from e
            except Exception as e:
                status = Status.UnknownError(
                    f"collective execution failed: {e!r}")
            if closer is None and self.timeline.enabled:
                self.timeline.activity_end_all(names)
                for e in entries:
                    self.timeline.end(e.tensor_name)
            self._cycle_bytes += sum(
                getattr(e.tensor, "nbytes", 0) for e in entries)
            if not status.in_progress():
                with htrace.span("hvd.complete", n=len(entries)):
                    for e in entries:
                        if e.callback:
                            e.callback(status)
# -- thread-affinity sanitizer (HOROVOD_TPU_THREADCHECK) ------------------
# Checked-field ids mirror the static thread-ownership analyzer's.
# _tenant_lane has no fixed owner: it legitimately migrates (main
# binds, background unwinds) under Runtime._lane_lock.
threadcheck.install(Runtime, "_tenant_lane",
                    "runtime.Runtime._tenant_lane")
