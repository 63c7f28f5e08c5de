"""Process lifecycle + identity: init / shutdown / rank / size / ...

Equivalent of the reference's ``HorovodBasics`` ctypes surface
(reference: horovod/common/__init__.py:51-154) and the C API behind it
(reference: horovod/common/operations.cc:1371-1426 horovod_init/rank/...).

Identity comes from the launcher's env (``HOROVOD_RANK``/``HOROVOD_SIZE``
+ ``HOROVOD_CONTROLLER_ADDR``/``PORT``, exported by hvdtpurun — see
horovod_tpu/run) the way the reference reads MPI's; with no env set,
``init()`` brings up a size-1 world, which still runs the full cycle
loop so async semantics/fusion/timeline behave identically at any size.

Multi-tenancy (common/tenancy.py, docs/multitenancy.md): one process
may host SEVERAL runtimes at once — the default world built here plus
any tenants created with ``create_tenant``. The module-level ops API
routes through :func:`active_runtime`, which a tenant's ``use()``
scope (a contextvar, so thread- and task-safe) points at its own
runtime; everything else keeps reading the default world.
"""

from __future__ import annotations

import atexit
import contextvars
from typing import Optional

from horovod_tpu.common import lockdep
from horovod_tpu.common import logging as hlog
from horovod_tpu.common import network
from horovod_tpu.common import trace as htrace
from horovod_tpu.common.config import Config
from horovod_tpu.common.controller import (
    Controller, LocalController, TcpCoordinator, TcpWorker,
)
from horovod_tpu.common.runtime import Runtime
from horovod_tpu.ops.local_ops import LocalBackend
from horovod_tpu.ops.operation_manager import OperationManager
from horovod_tpu.ops.socket_ops import SocketBackend
from horovod_tpu.ops.xla_ops import XlaMeshBackend

_lock = lockdep.lock("basics._lock")
_runtime: Optional[Runtime] = None

# The runtime the module-level ops API targets in THIS context: a
# tenant scope (tenancy.Tenant.use) sets it; None means the default
# world. A contextvar (not a plain global) so two threads driving two
# tenants never race each other's routing.
_active_runtime: "contextvars.ContextVar[Optional[Runtime]]" = \
    contextvars.ContextVar("horovod_tpu_active_runtime", default=None)


def _require_runtime() -> Runtime:
    if _runtime is None:
        raise ValueError(
            "horovod_tpu has not been initialized; run hvd.init() first.")
    return _runtime


def active_runtime() -> Runtime:
    """The runtime ops should target: the tenant scoped in via
    ``Tenant.use()`` when inside one, the default world otherwise."""
    rt = _active_runtime.get()
    return rt if rt is not None else _require_runtime()


def active_scope() -> str:
    """Auto-name counter scope of the active runtime ('' = default
    world) — per-tenant scoping keeps each tenant's
    ``<op>.noname.<n>`` sequence world-consistent no matter how its
    co-tenants' submissions interleave in this process."""
    rt = _active_runtime.get()
    return rt._tenant if rt is not None else ""


def _is_full_world(ranks, env_size: int) -> bool:
    """True when a comm list names the ENTIRE launched world — that
    sub-world IS the default world and may keep its env endpoint
    (and the launcher's reserved listener fd)."""
    return env_size > 0 and ranks == list(range(env_size))


def _build_runtime(cfg: Config, coordinator_listener=None,
                   elastic_ctx=None) -> Runtime:
    """Construct and start one runtime from a fully-resolved Config:
    controller (with the world id + tenant descriptor in the
    handshake), backends, op manager, autotuner. Shared by init()
    (the default world) and tenancy.create_tenant (tenant worlds —
    several may coexist in one process; nothing here touches module
    globals)."""
    secret = cfg.secret_key.encode() if cfg.secret_key else b""
    size = cfg.size if cfg.size > 0 else 1
    rank = cfg.rank if cfg.rank >= 0 else 0
    # Kernel-side wire knobs (docs/performance.md Layer 6): the
    # MSG_ZEROCOPY send threshold is a channel-layer module hook (it
    # gates sends made during rendezvous too), the reactor switch is
    # stamped on the controller below once it exists. Both are purely
    # rank-local recv/send disciplines — the wire stays byte-identical
    # — so heterogeneous worlds interoperate.
    network.set_zerocopy_threshold(cfg.zerocopy_send_threshold)
    elastic_port = elastic_ctx.port if elastic_ctx is not None \
        and size > 1 else None

    tenant_desc = None
    if cfg.world_id and rank == 0:
        from horovod_tpu.common import tenancy as _tenancy
        tenant_desc = _tenancy.descriptor_of(cfg)

    if size > 1 or cfg.metrics_enabled:
        # The native core is built or loaded by whoever asks first: the
        # TCP control plane, or the registry's build identity. Here,
        # where start-up can see what it costs.
        with htrace.span("hvd.init.native"):
            from horovod_tpu import native
            native.get()

    with htrace.span("hvd.init.rendezvous", n=size):
        if size == 1:
            controller: Controller = LocalController()
        elif rank == 0:
            listener = coordinator_listener
            if listener is None and cfg.controller_fd >= 0:
                import socket as _socket
                listener = _socket.socket(fileno=cfg.controller_fd)
            coord = TcpCoordinator(size, port=cfg.controller_port,
                                   secret=secret,
                                   start_timeout=cfg.start_timeout,
                                   listener=listener,
                                   hierarchical=cfg.hier_controller,
                                   heartbeat_interval=cfg.heartbeat_interval_s,
                                   heartbeat_timeout=cfg.heartbeat_timeout_s,
                                   elastic_port=elastic_port,
                                   world_id=cfg.world_id,
                                   tenant_desc=tenant_desc)
            coord.accept_workers()
            controller = coord
        else:
            if not cfg.controller_addr or not cfg.controller_port:
                raise ValueError(
                    "HOROVOD_CONTROLLER_ADDR/PORT must be set for "
                    "multi-process init (use the hvdtpurun launcher).")
            controller = TcpWorker(rank, size, cfg.controller_addr,
                                   cfg.controller_port, secret=secret,
                                   start_timeout=cfg.start_timeout,
                                   heartbeat_interval=cfg.heartbeat_interval_s,
                                   heartbeat_timeout=cfg.heartbeat_timeout_s,
                                   elastic_port=elastic_port,
                                   world_id=cfg.world_id)
    # Rank-local reactor opt-out (HOROVOD_TPU_REACTOR=0): the batched
    # recv discipline and the chunked-relay legs fall back to the
    # sequential/store-and-forward paths on THIS rank only.
    controller._reactor = cfg.reactor

    # Install the world-identical elastic membership (the
    # coordinator's broadcast endpoint map) for this generation.
    endpoints = getattr(controller, "elastic_endpoints", None)
    if elastic_ctx is not None and endpoints is not None:
        table = dict(endpoints)
        host0, port0 = table[0]
        if not host0:  # the coordinator's own placeholder entry
            table[0] = (cfg.controller_addr or "127.0.0.1", port0)
        elastic_ctx.apply_membership(
            elastic_ctx.membership.generation, controller.rank,
            controller.size, table)

    with htrace.span("hvd.init.runtime"):
        from horovod_tpu.ops.shm_ops import ShmBackend
        socket_backend = SocketBackend(controller, secret=secret,
                                       config=cfg)
        backends = [
            XlaMeshBackend(controller, config=cfg),
            ShmBackend(controller, fallback=socket_backend, config=cfg,
                       secret=secret),
            socket_backend,
            LocalBackend(lambda: controller.size),
        ]
        op_manager = OperationManager(backends)

        parameter_manager = None
        if cfg.autotune:
            from horovod_tpu.common.parameter_manager import (
                ParameterManager,
            )
            parameter_manager = ParameterManager(cfg, controller)

        rt = Runtime(cfg, controller, op_manager, parameter_manager)
        rt.start()
    return rt


def init(comm=None, config: Optional[Config] = None,
         coordinator_listener=None) -> None:
    """Initialize the runtime. ``comm`` accepts either a (rank, size)
    TUPLE for explicit worlds, or a LIST of global ranks forming a
    sub-world (reference: common/__init__.py:58-84 init(comm=ranks)):
    members are renumbered 0..len-1 in list order, the first listed
    rank's process hosts the sub-world's coordinator on a port derived
    from the membership, and processes NOT in the list come up as
    size-1 worlds so they can keep doing local work while the subset
    runs collectives. With ``comm=None`` identity comes from the
    environment. (For CONCURRENT sub-worlds with QoS scheduling and
    per-tenant observability, use ``hvd.create_tenant`` —
    docs/multitenancy.md.)

    ``coordinator_listener`` (rank 0 only) — an already-bound listening
    socket for the coordinator to adopt, closing the reserve/release/
    rebind race in launch layers that must publish the port before
    init. Launcher-spawned rank 0 can instead inherit the reservation
    as a file descriptor via ``HOROVOD_CONTROLLER_FD``.
    """
    with _lock:
        if _runtime is not None and _runtime.alive:
            return  # already initialized (reference: InitializeHorovodOnce
                    # test-and-set, operations.cc:1342-1360)
        cfg = config or Config.from_env()
        # The program's spans are armed from the configuration, so that
        # start-up itself is the first of them.
        htrace.bind_span_registry(None)
        htrace.arm_spans(cfg.metrics_enabled or bool(cfg.trace_path))
        with htrace.span("hvd.init") as sp:
            rt = _init_world(cfg, comm, coordinator_listener)
            sp.n = rt.controller.size


def _init_world(cfg: Config, comm, coordinator_listener) -> Runtime:
    """init()'s work on a resolved Config, under ``_lock``."""
    global _runtime
    hlog.set_level(cfg.log_level)
    # Publish the wire-compression latch (common/wire_dtype.py):
    # the framework-level Compression helpers become pass-throughs
    # while the negotiated data plane compresses, so gradients are
    # never cast twice.
    from horovod_tpu.common import wire_dtype as _wd
    _wd.set_active(_wd.wire_code_of(cfg.compression))
    if isinstance(comm, list):
        ranks = [int(r) for r in comm]
        env_size = cfg.size
        g_rank = cfg.rank if cfg.rank >= 0 else 0
        full_world = _is_full_world(ranks, env_size)
        # An inherited coordinator fd (launcher-reserved) serves
        # the FULL world's published endpoint; it is only valid
        # when this process leads that full world. Close it
        # otherwise or it lingers as a dead listener that eats the
        # port and black-holes connects.
        if cfg.controller_fd >= 0 and not (full_world
                                           and g_rank == 0):
            import os as _os
            try:
                _os.close(cfg.controller_fd)
            except OSError:
                pass
            cfg.controller_fd = -1
        if g_rank in ranks:
            cfg.rank = ranks.index(g_rank)
            cfg.size = len(ranks)
            if not full_world and cfg.controller_port:
                # The env endpoint belongs to the full world:
                # derive a per-membership port (tenancy.py) so a
                # sub-coordinator never collides with the full
                # world's listener OR another sub-world's — the
                # old first-rank-only derivation collided for two
                # subsets sharing a first rank, and a subset
                # anchored at global rank 0 squatted the fleet
                # port itself. Every member derives identically
                # from the full list; the world id below turns
                # any residual collision into a named handshake
                # error. On multi-host launches where the first
                # listed rank is not on the env-addr host, set
                # HOROVOD_CONTROLLER_ADDR to that rank's host
                # before calling init.
                from horovod_tpu.common import tenancy as _tenancy
                cfg.controller_port = _tenancy.derive_subworld_port(
                    cfg.controller_port, "", ranks)
                cfg.world_id = _tenancy.derive_world_id("", ranks)
        else:
            cfg.rank, cfg.size = 0, 1
    elif comm is not None:
        rank, size = comm
        cfg.rank, cfg.size = int(rank), int(size)
    secret = cfg.secret_key.encode() if cfg.secret_key else b""

    # Elastic worlds (HOROVOD_ELASTIC=1, common/elastic.py): bind
    # this process's re-rendezvous listener once; a respawned
    # joiner (HOROVOD_ELASTIC_JOIN=1) instead dials the advertised
    # coordinator endpoint and blocks until the next rendezvous
    # barrier admits it with a fresh dense rank.
    elastic_ctx = None
    if cfg.elastic_enabled and not isinstance(comm, list):
        from horovod_tpu.common import elastic as _elastic
        if cfg.elastic_join:
            assignment = _elastic.join_world(cfg, secret)
            cfg.rank = assignment.rank
            cfg.size = assignment.size
            cfg.controller_addr = assignment.controller_addr
            cfg.controller_port = assignment.controller_port
            cfg.controller_fd = -1
        if cfg.size > 1 or cfg.size <= 0:
            elastic_ctx = _elastic.ensure_context(cfg, secret)

    rt = _build_runtime(cfg,
                        coordinator_listener=coordinator_listener,
                        elastic_ctx=elastic_ctx)
    _runtime = rt
    from horovod_tpu import ops
    ops.reset_name_counters("")
    # Service mode (docs/multitenancy.md): rank 0 of a --service
    # fleet opens the tenant gate so jobs can attach/detach and
    # pull parameter snapshots without the fleet re-rendezvousing.
    if cfg.service_enabled and not cfg.world_id \
            and rt.controller.rank == 0:
        from horovod_tpu.common import tenancy as _tenancy
        _tenancy.start_service_gate(cfg, secret)
    hlog.debug(f"horovod_tpu initialized: rank {rt.controller.rank}"
               f" of {rt.controller.size}", rank=rt.controller.rank)
    return rt


def shutdown() -> None:
    """Stop the background loop; pending handles complete with
    SHUT_DOWN_ERROR (reference: operations.cc:1377-1383 horovod_shutdown,
    898-913)."""
    global _runtime
    with _lock:
        rt = _runtime
        if rt is None:
            return
        rt.request_shutdown()
        rt.join(timeout=30.0)
        _runtime = None
        from horovod_tpu.common import wire_dtype as _wd
        _wd.set_active(_wd.WIRE_NONE)
    from horovod_tpu.common import tenancy as _tenancy
    _tenancy.stop_service_gate()


atexit.register(shutdown)


def initialized() -> bool:
    return _runtime is not None and _runtime.alive


def runtime() -> Runtime:
    """Internal: the live Runtime (framework adapters use this)."""
    return _require_runtime()


def rank() -> int:
    return active_runtime().controller.topology.rank


def size() -> int:
    return active_runtime().controller.topology.size


def local_rank() -> int:
    return active_runtime().controller.topology.local_rank


def local_size() -> int:
    return active_runtime().controller.topology.local_size


def cross_rank() -> int:
    """Rank among hosts (reference: global_state.h cross_rank)."""
    return active_runtime().controller.topology.cross_rank


def cross_size() -> int:
    return active_runtime().controller.topology.cross_size


def is_homogeneous() -> bool:
    """True when every host runs the same number of ranks
    (reference: operations.cc:741-757)."""
    return active_runtime().controller.topology.is_homogeneous


def metrics() -> dict:
    """The live metrics view (HOROVOD_TPU_METRICS=1, docs/metrics.md):
    ``{"enabled": bool, "local": {...}, "world": {...}|None,
    "http_port": int|None}``. ``local`` is this rank's freshest
    registry snapshot; ``world`` is the control-tree aggregate and
    materializes only on rank 0 (the fold point); ``http_port`` is the
    live Prometheus endpoint's bound port when
    HOROVOD_TPU_METRICS_PORT enabled it. With metrics disabled the
    snapshots are empty and ``enabled`` is False. Inside a tenant
    scope this is the TENANT's view, with every series carrying its
    ``tenant`` label."""
    return active_runtime().metrics_view()


def note_traced(name: str, help: str, kinds: dict, labels: str = "") -> None:
    """``name{kind="<k>"<labels>}`` = n (gauge, max) for each of
    ``kinds``: what code that runs when a step is traced (a kernel's
    wrapper, the head loss's forward rule) says of the call being traced
    (docs/metrics.md), where a world with its metrics plane on is there
    to read it."""
    if not initialized():
        return
    reg = active_runtime().metrics
    if not reg.enabled:
        return
    for kind, n in kinds.items():
        reg.gauge(f'{name}{{kind="{kind}"{labels}}}', help,
                  agg="max").set(n)


def coordinator_threads_supported() -> bool:
    """Enqueues may come from any thread (the table is mutex-guarded),
    so multi-threaded use is always supported — unlike the reference,
    where this depends on MPI_THREAD_MULTIPLE
    (reference: operations.cc:674-693, common/__init__.py:150-154)."""
    return True


def mpi_threads_supported() -> bool:
    """Reference-compat alias for coordinator_threads_supported."""
    return coordinator_threads_supported()
