"""Environment-variable configuration surface.

The reference reads all knobs from ``HOROVOD_*`` env vars once at
background-thread startup (reference: horovod/common/operations.cc:626-639
helpers and 792-871). We keep the exact same names so scripts tuned for
the reference carry over, plus ``HOROVOD_TPU_*`` extensions for the
TPU-specific machinery.

This module is the ONLY place the runtime reads the environment —
enforced by ``python -m tools.hvdlint`` (the ``knobs`` analyzer):
modules that need a knob outside a ``Config`` snapshot (module-level
singletons, the launcher's child-env plumbing) go through the public
``env_str``/``env_int``/``env_float``/``env_bool`` helpers so
defaults, truthiness rules and the documentation contract stay in one
place.
"""

from __future__ import annotations

import dataclasses
import os


def env_str(name: str, default: str = "") -> str:
    v = os.environ.get(name)
    return default if v is None or v == "" else v


def env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        return default


def env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        return default


def env_bool(name: str, default: bool) -> bool:
    # Reference semantics: set and == "1" → on (operations.cc:626-631).
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return v.strip() in ("1", "true", "True", "TRUE", "yes", "on")


# Internal aliases kept for the from_env body below.
_env_int = env_int
_env_float = env_float
_env_bool = env_bool


@dataclasses.dataclass
class Config:
    """Snapshot of all runtime knobs, read once at init.

    Defaults follow the reference: 64 MiB fusion threshold
    (operations.cc:807-812), 5 ms cycle time (operations.cc:815-820),
    60 s stall check (operations.cc:543-624).
    """

    # Tensor fusion (reference: operations.cc:424-446, 807-820)
    fusion_threshold_bytes: int = 64 * 1024 * 1024
    cycle_time_ms: float = 5.0

    # Steady-state negotiation fast path (reference: the bit-vector
    # response cache upstream added as its coordinator scalability fix,
    # HOROVOD_CACHE_CAPACITY): previously negotiated responses are
    # kept in a world-coherent LRU cache and steady-state cycles
    # exchange one bit per cache slot instead of serialized Request
    # lists. Capacity 0 or HOROVOD_CACHE_ENABLED=0 disables (dynamic
    # graphs that never repeat tensor signatures gain nothing from
    # it). Both knobs must be identical on every rank.
    cache_enabled: bool = True
    cache_capacity: int = 1024
    # Fused speculative cycle: in bitmask steady state a rank attaches
    # its pre-packed fused allreduce buffers to the hit-mask gather
    # frame; the coordinator reduces inline and broadcasts grant +
    # result in one response frame — negotiation and the data plane
    # collapse into ONE world round-trip per step. Opportunistic and
    # per-cycle: any deviation (new tensor, shape change, a rank with
    # this knob off) falls back to the classic two-round cached path
    # for that cycle, so ranks may disagree on this knob safely.
    # Applies only when the star socket data plane would carry the
    # batch anyway (shm/ring/XLA-bound batches keep their plane).
    cache_speculative: bool = True

    # Zero-copy native data plane (docs/performance.md): steady-state
    # payloads move straight between sockets and numpy memory — the
    # persistent fusion arena feeds vectored sendmsg/recvmsg
    # (hvd_sendv/hvd_recv_into), receive sides land in preallocated
    # arrays, and the fused speculative cycle runs as ONE native call
    # per step (hvd_steady_cycle family). HOROVOD_TPU_ZERO_COPY=0
    # restores the PR 3 byte-copy paths (A/B lever for
    # collective_bench --steady-only; heterogeneous worlds are safe —
    # the wire format is identical either way).
    zero_copy: bool = True

    # Batched-submission reactor (docs/performance.md Layer 6): the
    # coordinator's N per-cycle peer recvs collapse into ONE native
    # readiness loop (hvd_gather_frames_batched — io_uring when the
    # build and kernel both have it, poll(2) otherwise, byte-identical
    # either way), and the hierarchical root/leaf relay legs switch to
    # the chunked cut-through relay (hvd_relay_frame).
    # HOROVOD_TPU_REACTOR=0 restores the sequential recv loop and the
    # store-and-forward relay; heterogeneous worlds are safe — the
    # wire format is identical either way.
    reactor: bool = True

    # Frames at or above this many payload bytes go out via
    # MSG_ZEROCOPY (kernel pins the pages instead of copying them into
    # the socket buffer; completion notifications are drained before
    # the send returns). Below it the plain copying send wins — the
    # pin/notify overhead beats the copy only for large frames.
    # 0 disables zerocopy sends entirely; the
    # hvd_zerocopy_copied_total counter surfaces kernels/paths that
    # silently degrade to copying (loopback always does).
    zerocopy_send_threshold: int = 64 * 1024

    # Ring data plane for the socket backend (TPU-native extension): host
    # payloads at or above this size ride the bandwidth-optimal 2-phase
    # ring (ops/ring.py) instead of the star through rank 0 — the TCP
    # rendering of what MPI_Allreduce gives the reference internally
    # (reference: mpi_operations.cc:25-84). Small messages stay on the
    # star (2 hops beats 2(N-1) lockstep hops when latency dominates,
    # the same size-based algorithm switch MPI/NCCL make internally).
    # Needs >= 3 ranks; -1 disables.
    ring_threshold_bytes: int = 1024 * 1024

    # Shared-memory data plane for same-host worlds (TPU-native rendering
    # of the reference's MPI_Win_allocate_shared staging,
    # mpi_operations.cc:179-329). HOROVOD_TPU_SHM=0 forces sockets.
    shm_enabled: bool = True

    # Wire-dtype gradient compression (docs/performance.md; upstream
    # analog: the Compression API's fp16-on-the-wire, deepened into a
    # negotiated per-request attribute — common/wire_dtype.py). This
    # rank PROPOSES the value for every float32/float64 allreduce; the
    # coordinator resolves the world's common denominator per fused
    # batch and broadcasts it in the Response, so heterogeneous knobs
    # degrade to the least aggressive proposal instead of diverging.
    # none | bf16 (recommended on TPU hosts: f32's exponent range at
    # half the bytes) | fp16 | int8 (with per-tensor error-feedback
    # residuals, Deep Gradient Compression style).
    compression: str = "none"

    # Overlap tier (docs/performance.md Layer 5): bucketed ready-order
    # dispatch + asynchronous in-flight steady cycles that hide
    # collective wire time under backward compute (DDP-bucket /
    # ByteScheduler lineage). HOROVOD_OVERLAP_BUCKETS splits every
    # grouped allreduce into that many size-balanced buckets (0 =
    # derive from HOROVOD_OVERLAP_BYTES; both 0 = bucketing off), each
    # negotiated and reduced as its OWN fused speculative / native
    # zero-copy cycle, so early buckets ride the wire while the
    # training thread still computes later gradients.
    # HOROVOD_OVERLAP_BYTES is the target bucket payload size when
    # deriving the count. All knobs are rank-local scheduling only —
    # the wire protocol is unchanged, so heterogeneous worlds degrade
    # to the synchronous path instead of diverging.
    overlap_buckets: int = 0
    overlap_bucket_bytes: int = 0
    # Asynchronous in-flight steady cycles: up to this many zero-copy
    # native steady cycles may be outstanding on the overlap runner
    # thread while the background loop packs the next bucket and the
    # training thread computes (handles complete out of band;
    # synchronize() only blocks on the tail bucket). 0 keeps every
    # cycle synchronous in the background loop. Needs the native
    # zero-copy plane; falls back silently without it.
    overlap_inflight: int = 2
    # Chunked pipelined transfer: the native steady worker splits a
    # compressed fused arena into wire chunks of this size and
    # interleaves the hvd_cast compression of chunk i+1 with the
    # kernel-buffered transmission of chunk i (one fused cast+HMAC
    # pass when frame auth is armed). 0 disables the chunk loop.
    overlap_chunk_bytes: int = 1024 * 1024

    # Two-level hierarchical allreduce (intra-host shm reduce ->
    # cross-host ring among local roots -> intra-host shm broadcast;
    # reference analog: NCCLHierarchicalAllreduce). HOROVOD_TWO_LEVEL=1
    # stamps multi-host allreduce batches at or above
    # two_level_threshold_bytes with the two-level algorithm; default
    # off keeps the existing shm-hier/star/ring routing untouched.
    # With HOROVOD_AUTOTUNE=1 the per-bucket (algorithm, wire dtype)
    # choice is tuned instead (common/parameter_manager.py).
    two_level: bool = False
    two_level_threshold_bytes: int = 0

    # Idle backoff for the background loop (TPU-native extension): after
    # a grace period of empty cycles the negotiation sleep ramps toward
    # this cap instead of waking every cycle_time_ms forever; enqueue
    # snaps it awake immediately. 0 disables (reference behavior).
    idle_backoff_ms: float = 25.0

    # Hierarchical collectives (reference: operations.cc:822-841); on TPU
    # this selects ICI×DCN mesh-axis-factored collectives (read by the
    # spmd hierarchical helpers; the flat TCP/XLA backends ignore it).
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False

    # Hierarchical CONTROL plane (TPU-native extension): on multi-host
    # worlds, each remote host's leaf ranks deliver their per-cycle
    # RequestLists to the host's local root, which forwards ONE
    # aggregate frame to the coordinator (and relays responses back),
    # so coordinator fan-in scales with n_hosts instead of world size —
    # the control-plane analog of the tree gather MPI_Gather gives the
    # reference for free (reference: operations.cc:1044-1065).
    # HOROVOD_TPU_HIER_CONTROLLER=0 forces the flat star.
    hier_controller: bool = True

    # XLA broadcast rendering: "psum" (masked psum — one fused
    # allreduce, ~2x payload per link but single-round and pipelined
    # by XLA; measured fastest at N>=8) or "tree" (binary-tree
    # ppermute chain — each device receives the payload exactly once,
    # N-1 payload transfers over the whole fabric vs the psum's ~2N,
    # at ceil(log2 N) sequential rounds of latency; wins on small or
    # congested worlds). See benchmarks/collective_bench.py
    # broadcast_rendering.
    xla_broadcast: str = "psum"

    # Timeline (reference: operations.cc:792-798)
    timeline_path: str = ""
    timeline_mark_cycles: bool = False

    # World trace plane (TPU-native extension; docs/tracing.md).
    # HOROVOD_TPU_TRACE=<path> arms clock-aligned cross-rank tracing:
    # every rank batches its cycle/exec spans into TAG_TRACE frames
    # that ride the control tree out-of-band like METRICS frames, and
    # rank 0 writes ONE merged Chrome-trace file at <path> with a
    # track per rank, timestamps corrected into the coordinator clock
    # and the world cycle number on every span. Must be set on every
    # rank (hvdtpurun --trace plumbs it). Empty disables — the
    # disabled path installs only no-op collector hooks.
    # (The flight recorder is separate and ON by default:
    # HOROVOD_TPU_FLIGHT / _FLIGHT_EVENTS / _FLIGHT_DIR are read by
    # common/trace.py at first use, deliberately not Config fields —
    # the recorder must survive elastic re-inits, like lockdep.)
    trace_path: str = ""
    trace_interval_s: float = 1.0

    # Metrics plane (TPU-native extension; the reference has no live
    # observability at all — timeline/stall/autotune are post-hoc).
    # HOROVOD_TPU_METRICS=1 arms per-rank counters/gauges/histograms
    # across the runtime, controller and op backends, world-aggregated
    # over the control tree every metrics_interval_s seconds. Default
    # OFF: the disabled path installs only no-op hooks (the
    # _NoOpTimeline pattern) so steady-state cost is zero.
    metrics_enabled: bool = False
    metrics_interval_s: float = 5.0
    # Rank-0 Prometheus endpoint: GET /metrics in text exposition
    # format. -1 disables the HTTP server; 0 binds an ephemeral port
    # (readable via horovod_tpu.metrics()["http_port"]).
    metrics_port: int = -1
    # Bind address for the endpoint. Default all interfaces (the
    # exporter convention — Prometheus usually scrapes from another
    # host); the endpoint is UNAUTHENTICATED, so on shared networks
    # set HOROVOD_TPU_METRICS_ADDR=127.0.0.1 and tunnel/proxy.
    metrics_addr: str = ""
    # Rank-0 JSONL snapshot log: one world-aggregated snapshot line
    # per interval. Empty disables.
    metrics_log: str = ""

    # Async collective completion (reference: cuda_operations.cc:148-179
    # detached finalizer threads + Status::InProgress). Off = the cycle
    # loop blocks until each collective's outputs are ready.
    async_completion: bool = True

    # Stall detection (reference: operations.cc:543-624)
    stall_check_disable: bool = False
    stall_check_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0

    # Runtime lockdep (HOROVOD_TPU_LOCKCHECK, docs/static_analysis.md)
    # is deliberately NOT a Config field: module-level locks exist
    # before any Config snapshot does, so common/lockdep.py reads the
    # knob once at first lock creation via env_str — a field here would
    # be an inert second source of truth.

    # Fail-fast liveness (TPU-native extension; the reference has no
    # peer-death detection — a SIGKILL'd rank leaves peers blocked in
    # MPI forever until the launcher kills the world). PING frames ride
    # idle gather waits every heartbeat_interval_s; a control channel
    # silent for heartbeat_timeout_s is declared dead and the world
    # aborts with WorldAbortedError. timeout <= 0 disables detection
    # (reference behavior).
    heartbeat_interval_s: float = 5.0
    heartbeat_timeout_s: float = 30.0

    # Autotune (reference: operations.cc:862-871, parameter_manager.cc)
    autotune: bool = False
    autotune_log: str = ""
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    autotune_bayes_opt_max_samples: int = 20
    autotune_gaussian_process_noise: float = 0.8

    # Logging (reference: logging.h, HOROVOD_LOG_LEVEL)
    log_level: str = "warning"
    log_hide_time: bool = False

    # Control plane (TPU-native: TCP coordination service instead of MPI).
    # Rendezvous address of the rank-0 coordinator.
    controller_addr: str = ""
    controller_port: int = 0
    # Inherited fd of a pre-bound coordinator listener (socket-activation
    # style): the launcher's TaskServer reserves the port and passes the
    # open socket to rank 0, so the endpoint it published can never be
    # stolen between reservation and bind.
    controller_fd: int = -1
    secret_key: str = ""
    start_timeout: float = 30.0

    # Native C++ core (horovod_tpu/native). On by default when the shared
    # library is importable; HOROVOD_TPU_NATIVE=0 forces pure-Python.
    native_core: bool = True

    # Elastic worlds (docs/fault_tolerance.md; upstream analog: Elastic
    # Horovod, v0.20). HOROVOD_ELASTIC=1 makes WorldAbortedError
    # recoverable: survivors re-rendezvous into a shrunk world within
    # elastic_window_s seconds (coordinator re-elected from the lowest
    # surviving rank when rank 0 died), respawned workers rejoin at
    # the next barrier, and training resumes after an
    # hvd.elastic.State re-broadcast. Below elastic_min_world members
    # the job aborts for real. Default OFF: the PR 2 fail-fast
    # behavior is untouched.
    elastic_enabled: bool = False
    elastic_window_s: float = 30.0
    elastic_min_world: int = 1
    # Fixed port for this rank's elastic listener (0 = ephemeral). The
    # launcher pins rank 0's so the join endpoint it advertises to
    # respawned workers stays stable across resizes.
    elastic_port: int = 0
    # Joiner identity (exported by the hvdtpurun --elastic supervision
    # loop on respawn): dial this elastic endpoint instead of the
    # normal HOROVOD_CONTROLLER_ADDR/PORT rendezvous.
    elastic_join: bool = False
    elastic_join_addr: str = ""
    elastic_join_port: int = 0

    # Self-operation (docs/fault_tolerance.md, common/selfop.py):
    # telemetry-driven supervision, preemption drains, the data-plane
    # rejoin sync and async in-cycle checkpoints. Like lockdep and the
    # flight recorder, the HOROVOD_SELFOP* / HOROVOD_PREEMPT* knobs
    # are deliberately NOT Config fields: the supervision policy,
    # signal handler and checkpoint writer are process-lifetime
    # singletons that must survive elastic re-inits, so selfop.py
    # reads them through the env_* helpers at use sites. The launcher
    # restart budget (HOROVOD_TPU_ELASTIC_RESTARTS) likewise lives in
    # run/launch.py — it configures the supervising parent, never a
    # rank.

    # Multi-tenant collective service (docs/multitenancy.md,
    # common/tenancy.py). A TENANT sub-world (hvd.create_tenant) gets
    # a nonzero world_id stamped on every control frame and a name
    # labelling its metrics/trace series; weight and quotas feed the
    # process-local QoS scheduler interleaving concurrent tenants'
    # negotiation cycles. The coordinator's weight/quota values are
    # broadcast in the handshake and win over rank-local env (like
    # the fusion threshold), so scheduling state is world-replicated.
    world_id: int = 0      # derived, never read from env
    tenant_name: str = ""  # derived, never read from env
    tenant_weight: float = 1.0
    tenant_quota_bytes_s: float = 0.0   # 0 = unlimited
    tenant_quota_cycles_s: float = 0.0  # 0 = unlimited
    # Service mode (hvdtpurun --service): rank 0 of the default world
    # opens the tenant service gate — jobs attach/detach and pull
    # parameter snapshots over a broadcast fanout without the fleet
    # re-rendezvousing. service_port 0 binds an ephemeral port.
    service_enabled: bool = False
    service_port: int = 0

    # Elastic/launcher-provided identity (reference: test/common.py:25-57
    # reads OMPI_COMM_WORLD_RANK; we read HOROVOD_RANK/SIZE first).
    rank: int = -1
    size: int = -1
    local_rank: int = -1
    local_size: int = -1

    @staticmethod
    def from_env() -> "Config":
        c = Config()
        c.fusion_threshold_bytes = _env_int(
            "HOROVOD_FUSION_THRESHOLD", c.fusion_threshold_bytes)
        c.cycle_time_ms = _env_float("HOROVOD_CYCLE_TIME", c.cycle_time_ms)
        c.cache_enabled = _env_bool("HOROVOD_CACHE_ENABLED",
                                    c.cache_enabled)
        c.cache_capacity = _env_int("HOROVOD_CACHE_CAPACITY",
                                    c.cache_capacity)
        c.cache_speculative = _env_bool("HOROVOD_CACHE_SPECULATIVE",
                                        c.cache_speculative)
        c.zero_copy = _env_bool("HOROVOD_TPU_ZERO_COPY", c.zero_copy)
        c.reactor = _env_bool("HOROVOD_TPU_REACTOR", c.reactor)
        c.zerocopy_send_threshold = _env_int(
            "HOROVOD_TPU_ZEROCOPY_SEND_THRESHOLD",
            c.zerocopy_send_threshold)
        c.ring_threshold_bytes = _env_int(
            "HOROVOD_TPU_RING_THRESHOLD", c.ring_threshold_bytes)
        c.shm_enabled = _env_bool("HOROVOD_TPU_SHM", c.shm_enabled)
        c.compression = os.environ.get("HOROVOD_COMPRESSION",
                                       c.compression).lower()
        # Validate through THE shared name table (wire_dtype.py) —
        # a second hardcoded list here would desync the moment a new
        # wire dtype lands. A typo must not silently run
        # uncompressed: wire_code_of raises naming the knob.
        from horovod_tpu.common import wire_dtype as _wdt
        _wdt.wire_code_of(c.compression)
        c.overlap_buckets = _env_int("HOROVOD_OVERLAP_BUCKETS",
                                     c.overlap_buckets)
        c.overlap_bucket_bytes = _env_int("HOROVOD_OVERLAP_BYTES",
                                          c.overlap_bucket_bytes)
        c.overlap_inflight = _env_int("HOROVOD_OVERLAP_INFLIGHT",
                                      c.overlap_inflight)
        c.overlap_chunk_bytes = _env_int("HOROVOD_OVERLAP_CHUNK_BYTES",
                                         c.overlap_chunk_bytes)
        c.two_level = _env_bool("HOROVOD_TWO_LEVEL", c.two_level)
        c.two_level_threshold_bytes = _env_int(
            "HOROVOD_TWO_LEVEL_THRESHOLD", c.two_level_threshold_bytes)
        c.idle_backoff_ms = _env_float(
            "HOROVOD_TPU_IDLE_BACKOFF", c.idle_backoff_ms)
        c.hierarchical_allreduce = _env_bool(
            "HOROVOD_HIERARCHICAL_ALLREDUCE", c.hierarchical_allreduce)
        c.hierarchical_allgather = _env_bool(
            "HOROVOD_HIERARCHICAL_ALLGATHER", c.hierarchical_allgather)
        c.hier_controller = _env_bool(
            "HOROVOD_TPU_HIER_CONTROLLER", c.hier_controller)
        c.xla_broadcast = os.environ.get("HOROVOD_XLA_BCAST",
                                         c.xla_broadcast).lower()
        if c.xla_broadcast not in ("psum", "tree"):
            # A typo must not silently pick a rendering — and per-rank
            # divergence would compile different collectives for the
            # same negotiated broadcast and hang the mesh.
            raise ValueError(
                f"HOROVOD_XLA_BCAST={c.xla_broadcast!r}: must be "
                "'psum' or 'tree'")
        c.timeline_path = os.environ.get("HOROVOD_TIMELINE", "")
        c.timeline_mark_cycles = _env_bool(
            "HOROVOD_TIMELINE_MARK_CYCLES", c.timeline_mark_cycles)
        c.trace_path = os.environ.get("HOROVOD_TPU_TRACE", "")
        c.trace_interval_s = _env_float(
            "HOROVOD_TPU_TRACE_INTERVAL", c.trace_interval_s)
        c.metrics_enabled = _env_bool("HOROVOD_TPU_METRICS",
                                      c.metrics_enabled)
        c.metrics_interval_s = _env_float(
            "HOROVOD_TPU_METRICS_INTERVAL", c.metrics_interval_s)
        c.metrics_port = _env_int("HOROVOD_TPU_METRICS_PORT",
                                  c.metrics_port)
        c.metrics_addr = os.environ.get("HOROVOD_TPU_METRICS_ADDR",
                                        c.metrics_addr)
        c.metrics_log = os.environ.get("HOROVOD_TPU_METRICS_LOG",
                                       c.metrics_log)
        c.async_completion = _env_bool(
            "HOROVOD_ASYNC_COMPLETION", c.async_completion)
        c.stall_check_disable = _env_bool(
            "HOROVOD_STALL_CHECK_DISABLE", c.stall_check_disable)
        c.stall_check_time_seconds = _env_float(
            "HOROVOD_STALL_CHECK_TIME_SECONDS", c.stall_check_time_seconds)
        c.stall_shutdown_time_seconds = _env_float(
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS",
            c.stall_shutdown_time_seconds)
        c.heartbeat_interval_s = _env_float(
            "HOROVOD_HEARTBEAT_INTERVAL", c.heartbeat_interval_s)
        c.heartbeat_timeout_s = _env_float(
            "HOROVOD_HEARTBEAT_TIMEOUT", c.heartbeat_timeout_s)
        c.autotune = _env_bool("HOROVOD_AUTOTUNE", c.autotune)
        c.autotune_log = os.environ.get("HOROVOD_AUTOTUNE_LOG", "")
        c.autotune_warmup_samples = _env_int(
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES", c.autotune_warmup_samples)
        c.autotune_steps_per_sample = _env_int(
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", c.autotune_steps_per_sample)
        c.autotune_bayes_opt_max_samples = _env_int(
            "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES",
            c.autotune_bayes_opt_max_samples)
        c.autotune_gaussian_process_noise = _env_float(
            "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE",
            c.autotune_gaussian_process_noise)
        c.log_level = os.environ.get("HOROVOD_LOG_LEVEL", c.log_level)
        c.log_hide_time = _env_bool("HOROVOD_LOG_HIDE_TIME", c.log_hide_time)
        c.controller_addr = os.environ.get("HOROVOD_CONTROLLER_ADDR", "")
        c.controller_port = _env_int("HOROVOD_CONTROLLER_PORT", 0)
        c.controller_fd = _env_int("HOROVOD_CONTROLLER_FD", c.controller_fd)
        c.secret_key = os.environ.get("HOROVOD_SECRET_KEY", "")
        c.start_timeout = _env_float("HOROVOD_START_TIMEOUT", c.start_timeout)
        c.native_core = _env_bool("HOROVOD_TPU_NATIVE", c.native_core)
        c.elastic_enabled = _env_bool("HOROVOD_ELASTIC",
                                      c.elastic_enabled)
        c.elastic_window_s = _env_float("HOROVOD_ELASTIC_WINDOW",
                                        c.elastic_window_s)
        c.elastic_min_world = _env_int("HOROVOD_ELASTIC_MIN_WORLD",
                                       c.elastic_min_world)
        c.elastic_port = _env_int("HOROVOD_TPU_ELASTIC_PORT",
                                  c.elastic_port)
        c.elastic_join = _env_bool("HOROVOD_ELASTIC_JOIN",
                                   c.elastic_join)
        c.elastic_join_addr = env_str("HOROVOD_ELASTIC_JOIN_ADDR",
                                      c.elastic_join_addr)
        c.elastic_join_port = _env_int("HOROVOD_ELASTIC_JOIN_PORT",
                                       c.elastic_join_port)
        c.tenant_weight = _env_float("HOROVOD_TENANT_WEIGHT",
                                     c.tenant_weight)
        c.tenant_quota_bytes_s = _env_float(
            "HOROVOD_TENANT_QUOTA_BYTES", c.tenant_quota_bytes_s)
        c.tenant_quota_cycles_s = _env_float(
            "HOROVOD_TENANT_QUOTA_CYCLES", c.tenant_quota_cycles_s)
        c.service_enabled = _env_bool("HOROVOD_TPU_SERVICE",
                                      c.service_enabled)
        c.service_port = _env_int("HOROVOD_TPU_SERVICE_PORT",
                                  c.service_port)
        c.rank = _env_int("HOROVOD_RANK", c.rank)
        c.size = _env_int("HOROVOD_SIZE", c.size)
        c.local_rank = _env_int("HOROVOD_LOCAL_RANK", c.local_rank)
        c.local_size = _env_int("HOROVOD_LOCAL_SIZE", c.local_size)
        return c
