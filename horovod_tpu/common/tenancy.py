"""Multi-tenant collective service: many jobs, one warm fleet.

The substrate PRs built — sub-worlds (``init(comm=[ranks])``), elastic
membership (PR 8), the metrics/trace planes (PRs 4/11) and an overlap
runner that already interleaves independent cycles (PR 10) — meets its
consumer here: the fleet stops being one job's private runtime and
becomes a shared collective *service* (docs/multitenancy.md).

Three coupled pieces:

1. **Tenants** — :func:`create_tenant` turns a sub-world into a
   first-class tenant: its own Runtime + controller on a coordinator
   port derived from the FULL membership and tenant name (two tenants
   can never squat one port, unlike the old first-rank-only
   derivation), a nonzero ``world_id`` stamped on every control frame
   (``wire.stamp_world``) so a frame that strays across worlds fails
   fast naming both ids, and per-tenant labels on the metrics/trace
   planes. One process may be a member of several tenants at once —
   each tenant is an independent tensor table driven by its own
   background loop, so a coordinator process drives several tenants'
   negotiation cycles concurrently.

2. **QoS-weighted scheduling** — every process hosts one
   :class:`TenantScheduler`; each tenant runtime's cycle loop acquires
   its :class:`_Lane` before negotiating a cycle with local work.
   Lanes interleave by *stride scheduling* over a virtual clock
   (weight 3 gets 3 cycles per weight-1 tenant's 1 when both are
   saturated) and carry token-bucket byte/cycle quotas fed from the
   live PR 4 metrics when armed (the runtime's own negotiated-byte
   count otherwise). An over-quota or out-weighted tenant's cycle is
   DEFERRED — bounded far under the heartbeat deadline by the same
   hold rule as every other hold in the cycle loop — never dropped,
   so pacing can never corrupt a world. The weight/quota values
   themselves are world-replicated: the tenant coordinator broadcasts
   its descriptor in the controller handshake and every member
   installs it through the ``@world_coherent`` apply path, so all
   ranks of a tenant pace under ONE policy no matter their local env.

3. **Service mode** — ``hvdtpurun --service`` (HOROVOD_TPU_SERVICE)
   opens the :class:`ServiceGate` on the fleet's rank 0: a listener in
   the mold of the PR 8 elastic listener whose manifest-style frames
   (wire.TENANT_*) let jobs ATTACH to and DETACH from the warm fleet
   without any re-rendezvous of the fleet's own world. The flagship
   path is batch inference: the training loop publishes parameter
   snapshots (:func:`publish_snapshot`), and an attached replica group
   pulls them over a broadcast FANOUT — the gate sends one copy to the
   group's root, which relays down a binary tree of the group's own
   listeners, so serving N replicas costs the fleet one send.
"""

from __future__ import annotations

import atexit
import contextlib
import socket
import threading
import time
import zlib
from typing import Dict, List, Optional

from horovod_tpu.common import lockdep
from horovod_tpu.common import threadcheck
from horovod_tpu.common import logging as hlog
from horovod_tpu.common import network
from horovod_tpu.common import wire
from horovod_tpu.common.config import Config
from horovod_tpu.common.invariants import world_coherent

# Channel tag for the service gate's dedicated sockets (its own
# connection namespace, like elastic.RDZV_TAG on rendezvous sockets).
SERVICE_TAG = 9

# Derived-port spread for sub-world coordinators. Must comfortably
# exceed any realistic tenant count on one fleet while keeping
# base+offset a valid port.
_PORT_SPREAD = 8191


def derive_world_id(name: str, ranks) -> int:
    """Nonzero u32 identity of a (tenant, membership) pair — stamped
    on every control frame of the sub-world. Deterministic from
    arguments every member knows, so all ranks derive it identically
    with no extra negotiation."""
    key = f"{name}|{','.join(str(int(r)) for r in ranks)}"
    return 1 + (zlib.crc32(key.encode()) % 0xFFFFFFFE)


def derive_subworld_port(base_port: int, name: str, ranks) -> int:
    """Coordinator port for a sub-world, derived from the FULL
    membership and tenant name. The pre-tenancy derivation keyed on
    ``ranks[0]`` alone — two sub-worlds anchored at the same first
    rank (or a rank-0-anchored subset squatting the fleet's own env
    port) collided; worse, the collision handed one tenant's frames
    to another's coordinator. Now distinct (name, membership) pairs
    spread over ``_PORT_SPREAD`` ports and the world-id handshake
    check turns any residual collision into a named startup error
    instead of silent corruption."""
    key = f"{name}|{','.join(str(int(r)) for r in ranks)}"
    port = base_port + 1 + (zlib.crc32(key.encode()) % _PORT_SPREAD)
    if port > 65535:
        # High ephemeral base: fold back into the registered range,
        # still deterministic for every member, still != base.
        port = 1024 + ((port - 65536) % (65535 - 1024))
        if port == base_port:
            port += 1
    return port


# ---------------------------------------------------------------------------
# QoS-weighted tenant scheduling
# ---------------------------------------------------------------------------

class _Lane:
    """One tenant's seat in the process-local scheduler. All state is
    guarded by the scheduler's condition; the runtime's background
    thread is the only caller of acquire/note_cycle."""

    def __init__(self, sched: "TenantScheduler", world_id: int,
                 name: str, weight: float, quota_bytes_s: float,
                 quota_cycles_s: float, live_bytes_fn=None,
                 metrics=None):
        self._sched = sched
        self.world_id = world_id
        self.name = name
        self.weight = max(float(weight), 1e-6)
        self.quota_bytes_s = max(float(quota_bytes_s), 0.0)
        self.quota_cycles_s = max(float(quota_cycles_s), 0.0)
        # Token buckets: one second of burst capacity; note_cycle
        # charges AFTER the fact, so a bucket can go negative and the
        # next acquire waits out the deficit.
        self.tokens_b = self.quota_bytes_s
        self.tokens_c = self.quota_cycles_s
        self.refill_t = time.monotonic()
        # Stride scheduling over a shared virtual clock: each granted
        # cycle advances vtime by 1/weight; the wanting lane with the
        # smallest vtime goes next. ``last_done`` drives the
        # idle-credit reset (see TenantScheduler._acquire).
        self.vtime = 0.0
        self.want = False
        self.last_done = time.monotonic()
        # Live quota source (the PR 4 metrics plane): a callable
        # returning this tenant's cumulative wire-byte total; when
        # armed it overrides the runtime-reported per-cycle bytes.
        self._live_bytes_fn = live_bytes_fn
        self._live_bytes_seen: Optional[float] = None
        # Observability (no-op metric objects when the plane is off).
        self._m_deferrals = getattr(metrics, "deferrals", None)
        self._m_deferred_s = getattr(metrics, "deferred_s", None)
        self._m_cycles = getattr(metrics, "cycles", None)
        self.deferrals = 0
        self.deferred_s = 0.0
        self.cycles = 0
        self.bytes = 0

    # Called by Runtime._run_loop_once (see bind_tenant_lane).
    def acquire(self, max_hold_s: float) -> float:
        return self._sched._acquire(self, max_hold_s)

    def note_cycle(self, reported_bytes: int) -> None:
        nbytes = int(reported_bytes)
        if self._live_bytes_fn is not None:
            try:
                total = float(self._live_bytes_fn())
                if self._live_bytes_seen is not None:
                    nbytes = max(0, int(total - self._live_bytes_seen))
                self._live_bytes_seen = total
            except Exception:
                pass  # metrics plane mid-teardown: keep the report
        self._sched._note(self, nbytes)

    def status_line(self) -> str:
        return (f"weight {self.weight:g}, {self.cycles} cycles, "
                f"{self.bytes} B negotiated, {self.deferrals} "
                f"deferrals ({self.deferred_s:.2f}s deferred)")


class TenantScheduler:
    """Process-local arbiter interleaving concurrent tenants' cycles.

    Pacing is rank-local (like the burst/idle/overlap holds): every
    member of a tenant runs the same world-replicated weights and
    quotas, so their independent decisions agree to within one cycle,
    and a rank that defers simply delays the blocking gather — bounded
    far under the heartbeat deadline, it can never be mistaken for
    death or corrupt a frame."""

    # A lane quiet for longer than this re-enters at the top of the
    # virtual clock: no credit accrues while idle, so a freshly-busy
    # tenant cannot monopolize the fleet to "catch up" with one that
    # was running all along. Saturated lanes (sub-cycle gaps between
    # note_cycle and the next acquire) are NEVER reset — the stride
    # differential between their clocks IS the weighting mechanism.
    _IDLE_RESET_S = 0.25

    # An out-weighted lane yields to its contenders for at most this
    # long in one acquire. The decisions are rank-local, and under load
    # two ranks' clocks drift apart: rank A then holds tenant X for Y
    # while rank B holds Y for X, each granted cycle sits in a gather
    # that waits for the peer's held lane, and only a hold running out
    # ends it. A contender's own cycle takes milliseconds, so this
    # costs the weights nothing; a quota's deferral keeps the whole of
    # ``max_hold_s``.
    _INTERLEAVE_HOLD_S = 0.1

    def __init__(self):
        self._cv = lockdep.condition("tenancy.TenantScheduler._lock")
        self._lanes: List[_Lane] = []

    def _vmax(self) -> float:
        return max((l.vtime for l in self._lanes), default=0.0)

    def register(self, world_id: int, name: str, weight: float,
                 quota_bytes_s: float, quota_cycles_s: float,
                 live_bytes_fn=None, metrics=None) -> _Lane:
        lane = _Lane(self, world_id, name, weight, quota_bytes_s,
                     quota_cycles_s, live_bytes_fn=live_bytes_fn,
                     metrics=metrics)
        with self._cv:
            # a newcomer starts at the top of the clock: no credit
            # for the time before it existed
            lane.vtime = self._vmax()
            self._lanes.append(lane)
        return lane

    def unregister(self, lane: _Lane) -> None:
        with self._cv:
            if lane in self._lanes:
                self._lanes.remove(lane)
            self._cv.notify_all()

    def lanes(self) -> List[_Lane]:
        with self._cv:
            return list(self._lanes)

    def _refill(self, lane: _Lane, now: float) -> None:
        dt = max(0.0, now - lane.refill_t)
        lane.refill_t = now
        if lane.quota_bytes_s:
            lane.tokens_b = min(lane.quota_bytes_s,
                                lane.tokens_b + dt * lane.quota_bytes_s)
        if lane.quota_cycles_s:
            lane.tokens_c = min(
                lane.quota_cycles_s,
                lane.tokens_c + dt * lane.quota_cycles_s)

    def _quota_wait(self, lane: _Lane) -> float:
        """Seconds until the lane's most-indebted bucket refills to
        non-negative; 0 when within quota."""
        wait = 0.0
        if lane.quota_bytes_s and lane.tokens_b < 0:
            wait = max(wait, -lane.tokens_b / lane.quota_bytes_s)
        if lane.quota_cycles_s and lane.tokens_c < 0:
            wait = max(wait, -lane.tokens_c / lane.quota_cycles_s)
        return wait

    def _solvent_at(self, lane: _Lane, now: float) -> bool:
        """Would ``lane``'s buckets be non-negative at ``now``?
        Projected WITHOUT mutating (refills are lazy, applied by each
        lane's own acquire) — used to exclude quota-parked lanes from
        the weighted-interleave contention check: a lane that CANNOT
        run must never defer one that can (priority inversion — the
        unlimited co-tenant of a tightly-capped tenant would otherwise
        crawl at the capped tenant's pace)."""
        dt = max(0.0, now - lane.refill_t)
        if lane.quota_bytes_s and \
                lane.tokens_b + dt * lane.quota_bytes_s < 0:
            return False
        if lane.quota_cycles_s and \
                lane.tokens_c + dt * lane.quota_cycles_s < 0:
            return False
        return True

    def _acquire(self, lane: _Lane, max_hold_s: float) -> float:
        """Block until it is ``lane``'s turn (weighted interleave) and
        its quota buckets are solvent, or until ``max_hold_s`` passes
        — the cycle then proceeds regardless (deferred, never lost).
        Returns the seconds spent deferred."""
        t0 = time.monotonic()
        deadline = t0 + max(0.0, max_hold_s)
        deferred = 0.0
        with self._cv:
            # ``want`` marks the lane's whole BUSY period — from here
            # until note_cycle reports the cycle done — not just this
            # wait. A lane merely mid-cycle still counts as a
            # contender, or back-to-back fast cycles would never
            # overlap another lane's wait window and weights could
            # not bite.
            lane.want = True
            if t0 - lane.last_done > self._IDLE_RESET_S:
                lane.vtime = max(lane.vtime, self._vmax())
            try:
                while True:
                    now = time.monotonic()
                    self._refill(lane, now)
                    if now >= deadline:
                        break
                    wait = self._quota_wait(lane)
                    if wait <= 0.0:
                        contender = any(
                            o.want and o.vtime < lane.vtime - 1e-12
                            and self._solvent_at(o, now)
                            for o in self._lanes if o is not lane)
                        if not contender \
                                or now - t0 >= self._INTERLEAVE_HOLD_S:
                            break
                        # Out-weighted: wait for a competitor's grant
                        # to move the clock (notify below), re-check
                        # at least every 50 ms in case it went idle.
                        wait = 0.05
                    self._cv.wait(min(wait, deadline - now))
            finally:
                now = time.monotonic()
                deferred = now - t0
                # Charge the granted cycle to the virtual clock.
                lane.vtime += 1.0 / lane.weight
                if lane.quota_cycles_s:
                    lane.tokens_c -= 1.0
                if deferred > 0.001:
                    lane.deferrals += 1
                    lane.deferred_s += deferred
                    if lane._m_deferrals is not None:
                        lane._m_deferrals.inc()
                        lane._m_deferred_s.inc(deferred)
                self._cv.notify_all()
        return deferred

    def _note(self, lane: _Lane, nbytes: int) -> None:
        with self._cv:
            lane.want = False  # busy period over (see _acquire)
            lane.last_done = time.monotonic()
            lane.cycles += 1
            lane.bytes += nbytes
            if lane._m_cycles is not None:
                lane._m_cycles.inc()
            if lane.quota_bytes_s:
                lane.tokens_b -= nbytes
            self._cv.notify_all()


_SCHEDULER: Optional[TenantScheduler] = None
_SCHED_LOCK = lockdep.lock("tenancy._SCHED_LOCK")


def scheduler() -> TenantScheduler:
    """The process-wide tenant scheduler (created on first use)."""
    global _SCHEDULER
    if _SCHEDULER is None:
        with _SCHED_LOCK:
            if _SCHEDULER is None:
                _SCHEDULER = TenantScheduler()
    return _SCHEDULER


# ---------------------------------------------------------------------------
# Tenants
# ---------------------------------------------------------------------------

class _LaneMetrics:
    """Per-tenant scheduler metrics on the tenant runtime's registry
    (no-op objects when the plane is off — the NOOP_METRIC pattern)."""

    def __init__(self, registry):
        self.deferrals = registry.counter(
            "hvd_tenant_deferrals_total",
            "cycles of this tenant the QoS scheduler deferred")
        self.deferred_s = registry.counter(
            "hvd_tenant_deferred_seconds_total",
            "total time this tenant's cycles spent deferred")
        self.cycles = registry.counter(
            "hvd_tenant_cycles_total",
            "negotiation cycles this tenant completed with local work")


class Tenant:
    """One job's seat on the shared fleet: an independent runtime over
    a sub-world, scheduled against its co-tenants. Collective methods
    mirror the top-level ops API and route to THIS tenant's runtime."""

    def __init__(self, name: str, cfg: Config, runtime):
        self.name = name
        self.world_id = cfg.world_id
        self._cfg = cfg
        self._runtime = runtime
        self._lane: Optional[_Lane] = None
        # The world-replicated scheduling descriptor: weight/quotas
        # every member paces under. Installed ONLY from the
        # coordinator's handshake broadcast (_apply_descriptor) — a
        # rank-local env value never reaches the scheduler directly.
        self._desc: Optional[dict] = None  # hvdlint: world-replicated

    @world_coherent
    def _apply_descriptor(self, desc: dict) -> None:
        """Install the coordinator-broadcast weight/quota descriptor —
        world-identical input by construction (every member decodes
        the same handshake blob), so tenant scheduling state can
        never diverge across ranks."""
        self._desc = dict(desc)

    def _bind_lane(self) -> None:
        desc = self._desc or {}
        reg = self._runtime.metrics
        live_fn = None
        if getattr(reg, "enabled", False):
            # Quota enforcement from the LIVE metrics plane: the same
            # counters /metrics and hvd.metrics() expose. The counter
            # objects are memoized by name, so these are the very
            # instances the data plane increments.
            counters = [reg.counter(n) for n in (
                "hvd_bytes_allreduced_total",
                "hvd_bytes_allgathered_total",
                "hvd_bytes_broadcast_total",
                "hvd_bytes_alltoall_total",
                "hvd_bytes_reducescattered_total")]
            live_fn = lambda: sum(c.value() for c in counters)
        reg.gauge("hvd_tenant_weight",
                  "QoS weight of this tenant (world-replicated)"
                  ).set(desc.get("weight", 1.0))
        self._lane = scheduler().register(
            self.world_id, self.name,
            desc.get("weight", 1.0),
            desc.get("quota_bytes_s", 0.0),
            desc.get("quota_cycles_s", 0.0),
            live_bytes_fn=live_fn,
            metrics=_LaneMetrics(reg))
        self._runtime.bind_tenant_lane(self._lane)

    # -- identity --------------------------------------------------------
    @property
    def rank(self) -> int:
        return self._runtime.controller.topology.rank

    @property
    def size(self) -> int:
        return self._runtime.controller.topology.size

    @property
    def alive(self) -> bool:
        rt = self._runtime
        return rt is not None and rt.alive

    def lane_stats(self) -> dict:
        lane = self._lane
        if lane is None:
            return {}
        return {"cycles": lane.cycles, "bytes": lane.bytes,
                "deferrals": lane.deferrals,
                "deferred_s": lane.deferred_s,
                "weight": lane.weight}

    def metrics(self) -> dict:
        return self._runtime.metrics_view()

    # -- op routing ------------------------------------------------------
    @contextlib.contextmanager
    def use(self):
        """Route the module-level ops API (hvd.allreduce, ...) to this
        tenant's runtime within the block — the mechanism behind every
        collective method below."""
        from horovod_tpu.common import basics
        token = basics._active_runtime.set(self._runtime)
        try:
            yield self
        finally:
            basics._active_runtime.reset(token)

    def _op(self, fname, *args, **kwargs):
        from horovod_tpu import ops as hops
        with self.use():
            return getattr(hops, fname)(*args, **kwargs)

    def allreduce(self, *a, **kw): return self._op("allreduce", *a, **kw)
    def allreduce_async(self, *a, **kw):
        return self._op("allreduce_async", *a, **kw)
    def grouped_allreduce(self, *a, **kw):
        return self._op("grouped_allreduce", *a, **kw)
    def grouped_allreduce_async(self, *a, **kw):
        return self._op("grouped_allreduce_async", *a, **kw)
    def allgather(self, *a, **kw): return self._op("allgather", *a, **kw)
    def allgather_async(self, *a, **kw):
        return self._op("allgather_async", *a, **kw)
    def broadcast(self, *a, **kw): return self._op("broadcast", *a, **kw)
    def broadcast_async(self, *a, **kw):
        return self._op("broadcast_async", *a, **kw)
    def alltoall(self, *a, **kw): return self._op("alltoall", *a, **kw)
    def reducescatter(self, *a, **kw):
        return self._op("reducescatter", *a, **kw)
    def barrier(self, *a, **kw): return self._op("barrier", *a, **kw)
    def poll(self, handle): return self._op("poll", handle)
    def synchronize(self, handle): return self._op("synchronize", handle)

    # -- lifecycle -------------------------------------------------------
    def shutdown(self) -> None:
        rt, self._runtime = self._runtime, None
        if rt is None:
            return
        rt.request_shutdown()
        rt.join(timeout=30.0)
        if self._lane is not None:
            scheduler().unregister(self._lane)
            self._lane = None
        from horovod_tpu import ops as _ops
        _ops.reset_name_counters(self.name)
        with _TENANTS_LOCK:
            _TENANTS.pop(self.name, None)


@world_coherent
def _install_descriptor(tenant: Tenant, desc: dict) -> None:
    """Install the tenant's scheduling descriptor — world-identical
    input by construction: members decode the coordinator's handshake
    blob, and the coordinator installs the very values it broadcast
    (hvdlint's world-coherence analyzer anchors the chain here)."""
    tenant._apply_descriptor(desc)


_TENANTS: Dict[str, Tenant] = {}
_TENANTS_LOCK = lockdep.lock("tenancy._TENANTS_LOCK")


def create_tenant(name: str, comm, weight: Optional[float] = None,
                  quota_bytes_s: Optional[float] = None,
                  quota_cycles_s: Optional[float] = None,
                  config: Optional[Config] = None) -> Optional[Tenant]:
    """Bring up tenant ``name`` over the global ranks in ``comm``.

    Every member process calls this with the SAME (name, comm);
    non-members get ``None`` back and are untouched (unlike
    ``init(comm=...)``, which gives abstainers a size-1 world — a
    tenant is opt-in). Weight and quotas may be set per call or via
    HOROVOD_TENANT_WEIGHT / HOROVOD_TENANT_QUOTA_BYTES /
    HOROVOD_TENANT_QUOTA_CYCLES; whatever the tenant COORDINATOR
    resolves is broadcast in the handshake and wins on every member
    (world-replicated scheduling state)."""
    from horovod_tpu.common import basics
    ranks = [int(r) for r in comm]
    if not ranks:
        raise ValueError("a tenant needs at least one member rank")
    cfg = config or Config.from_env()
    g_rank = cfg.rank if cfg.rank >= 0 else 0
    if g_rank not in ranks:
        return None
    with _TENANTS_LOCK:
        if name in _TENANTS:
            raise ValueError(
                f"tenant {name!r} already exists in this process")
    if weight is not None:
        cfg.tenant_weight = float(weight)
    if quota_bytes_s is not None:
        cfg.tenant_quota_bytes_s = float(quota_bytes_s)
    if quota_cycles_s is not None:
        cfg.tenant_quota_cycles_s = float(quota_cycles_s)
    cfg.tenant_name = name
    cfg.world_id = derive_world_id(name, ranks)
    cfg.rank = ranks.index(g_rank)
    cfg.size = len(ranks)
    if cfg.controller_port:
        cfg.controller_port = derive_subworld_port(
            cfg.controller_port, name, ranks)
    # The launcher's reserved listener fd serves the DEFAULT world's
    # endpoint; a tenant coordinator always binds its derived port.
    cfg.controller_fd = -1
    # Tenants ride the fleet's warm processes; elastic re-rendezvous
    # belongs to the default world that owns those processes.
    cfg.elastic_enabled = False
    cfg.elastic_join = False
    # Fresh auto-name counters for this tenant's scope: a re-created
    # same-name tenant (or one whose member process was respawned)
    # must start its <op>.noname.<n> sequence at 0 on EVERY rank, or
    # surviving ranks' stale counters would diverge tensor names and
    # stall the new world.
    from horovod_tpu import ops as _ops
    _ops.reset_name_counters(name)
    rt = basics._build_runtime(cfg)
    tenant = Tenant(name, cfg, rt)
    desc = getattr(rt.controller, "tenant_desc", None)
    if desc is None:
        # Tenant coordinator (or a 1-member tenant): its own resolved
        # values ARE the broadcast descriptor.
        desc = descriptor_of(cfg)
    _install_descriptor(tenant, desc)
    tenant._bind_lane()
    with _TENANTS_LOCK:
        _TENANTS[name] = tenant
    hlog.debug(f"tenant {name!r} up: rank {tenant.rank} of "
               f"{tenant.size}, world {cfg.world_id:#010x}",
               rank=tenant.rank)
    return tenant


def descriptor_of(cfg: Config) -> dict:
    """The world-replicated scheduling descriptor the tenant
    coordinator broadcasts in its controller handshake."""
    return {"name": cfg.tenant_name,
            "world_id": cfg.world_id,
            "weight": cfg.tenant_weight,
            "quota_bytes_s": cfg.tenant_quota_bytes_s,
            "quota_cycles_s": cfg.tenant_quota_cycles_s}


def tenants() -> Dict[str, Tenant]:
    with _TENANTS_LOCK:
        return dict(_TENANTS)


def _shutdown_all() -> None:
    for t in list(tenants().values()):
        try:
            t.shutdown()
        except Exception:
            pass
    stop_service_gate()


# Registered AFTER basics registers its atexit(shutdown), so tenants
# (and the service gate) tear down BEFORE the default world does.
atexit.register(_shutdown_all)


# ---------------------------------------------------------------------------
# Service mode: attach / detach / snapshot fanout
# ---------------------------------------------------------------------------

_SNAPSHOT_POLL_S = 0.25


class ServiceGate:
    """Rank 0's attach point for service-mode jobs (hvdtpurun
    --service). Accepts TENANT_ATTACH manifests on a dedicated
    listener — the service-plane sibling of the PR 8 elastic listener,
    same Channel framing and manifest-shaped codecs — leases each
    replica group a member table once the group is complete, serves
    published parameter snapshots to group ROOTS (one send per group;
    the group fans out among itself), and lets replicas detach with an
    ACK. The fleet's own world never re-rendezvouses: everything here
    rides daemon threads beside the training loop."""

    def __init__(self, port: int = 0, secret: bytes = b""):
        self._secret = secret
        self._server = network.listen(port)
        self.port = self._server.getsockname()[1]
        self._cv = lockdep.condition("tenancy.ServiceGate._lock")
        self._closing = False
        # tenant name -> {"group": n, "members": {replica: (host, port)},
        #                 "chans": {replica: Channel}, "lease": id}
        self._groups: Dict[str, dict] = {}
        self._lease_seq = 0
        self._snapshot: Optional[bytes] = None
        self._snapshot_version = 0
        self.attaches = 0
        self.detaches = 0
        self.snapshots_served = 0
        self._threads: List[threading.Thread] = []
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="hvd-service-gate",
            daemon=True)
        self._accept_thread.start()

    # -- publishing ------------------------------------------------------
    def publish(self, params: Dict, version: Optional[int] = None
                ) -> int:
        """Store the latest parameter snapshot (serialized once, so N
        attached groups share one encoding). Returns the version."""
        with self._cv:
            v = version if version is not None \
                else self._snapshot_version + 1
            self._snapshot = wire.serialize_tenant_snapshot(v, params)
            self._snapshot_version = v
            self._cv.notify_all()
            return v

    # -- accept / per-replica service ------------------------------------
    def _accept_loop(self) -> None:
        threadcheck.register_role("hvd-service-gate")
        self._server.settimeout(0.5)
        while not self._closing:
            try:
                sock, _ = self._server.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            t = threading.Thread(target=self._serve_replica,
                                 args=(sock,), daemon=True)
            t.start()
            # prune finished servers so a long-lived gate (the whole
            # point of service mode) never grows this list unboundedly
            self._threads = [x for x in self._threads if x.is_alive()]
            self._threads.append(t)

    def _serve_replica(self, sock) -> None:
        threadcheck.register_role("serve_replica")
        ch = None
        tenant = replica = None
        try:
            sock.settimeout(10.0)
            ch = network.Channel(sock, self._secret)
            tag, payload = ch.recv()
            if tag != SERVICE_TAG:
                raise ConnectionError(f"unexpected tag {tag}")
            m = wire.parse_tenant_attach(payload)
            if m["kind"] != wire.TENANT_ATTACH:
                raise ConnectionError(
                    f"expected attach, got kind {m['kind']}")
            tenant, replica = m["tenant"], m["replica"]
            group = max(1, m["group"])
            # The dialer's observed address overrides the self-report,
            # exactly like the elastic manifest path: it is what this
            # host can provably route back to.
            host = sock.getpeername()[0] or m["host"]
            with self._cv:
                g = self._groups.setdefault(
                    tenant, {"group": group, "members": {},
                             "chans": {}, "lease": 0})
                g["group"] = group
                g["members"][replica] = (host, m["port"])
                g["chans"][replica] = ch
                self.attaches += 1
                complete = len(g["members"]) >= g["group"]
                if complete and not g["lease"]:
                    self._lease_seq += 1
                    g["lease"] = self._lease_seq
                if complete:
                    self._cv.notify_all()
                else:
                    # Park until the group completes (or the gate
                    # closes) — the lease must carry the full member
                    # table for the fanout tree.
                    while (len(g["members"]) < g["group"]
                           and not self._closing):
                        self._cv.wait(0.5)
                members = [g["members"][i]
                           for i in sorted(g["members"])]
                lease = g["lease"]
            from horovod_tpu.common import elastic as _elastic
            sock.settimeout(None)
            ch.send(wire.serialize_tenant_lease(
                wire.TENANT_LEASE, 0, _elastic.generation(), lease,
                len(members), members), SERVICE_TAG)
            while True:
                tag, payload = ch.recv()
                if tag != SERVICE_TAG:
                    raise ConnectionError(f"unexpected tag {tag}")
                kind = payload[0] if payload else None
                if kind == wire.TENANT_DETACH:
                    with self._cv:
                        self.detaches += 1
                        g = self._groups.get(tenant)
                        if g is not None:
                            g["members"].pop(replica, None)
                            g["chans"].pop(replica, None)
                            if not g["members"]:
                                self._groups.pop(tenant, None)
                    ch.send(wire.serialize_tenant_lease(
                        wire.TENANT_ACK, 0, 0, lease, 0, []),
                        SERVICE_TAG)
                    return
                if kind != wire.TENANT_SNAPSHOT_REQ:
                    raise ConnectionError(
                        f"unexpected service frame kind {kind}")
                req = wire.parse_tenant_attach(payload)
                min_version = max(0, req["replica"])  # field reuse
                with self._cv:
                    while (self._snapshot is None
                           or self._snapshot_version < min_version) \
                            and not self._closing:
                        self._cv.wait(_SNAPSHOT_POLL_S)
                    snap = self._snapshot
                    self.snapshots_served += 1
                if snap is None:
                    raise ConnectionError("gate closed")
                ch.send(snap, SERVICE_TAG)
        except (ConnectionError, OSError, ValueError) as e:
            hlog.debug(f"service replica connection ended: {e}")
        finally:
            if ch is not None:
                try:
                    ch.close()
                except OSError:
                    pass

    def stats(self) -> dict:
        with self._cv:
            return {"attaches": self.attaches,
                    "detaches": self.detaches,
                    "snapshots_served": self.snapshots_served,
                    "groups": {t: len(g["members"])
                               for t, g in self._groups.items()},
                    "snapshot_version": self._snapshot_version}

    def close(self) -> None:
        with self._cv:
            self._closing = True
            self._cv.notify_all()
            # closing only the listener would leave every connected
            # replica's service thread parked in a timeout-less recv
            # until process exit — close their channels so those
            # threads unblock and drain
            chans = [ch for g in self._groups.values()
                     for ch in g["chans"].values()]
        for ch in chans:
            try:
                ch.close()
            except OSError:
                pass
        try:
            self._server.close()
        except OSError:
            pass


_GATE: Optional[ServiceGate] = None
_GATE_LOCK = lockdep.lock("tenancy._GATE_LOCK")


def start_service_gate(cfg: Config, secret: bytes) -> ServiceGate:
    """Open the service gate (init() calls this on the default world's
    rank 0 when HOROVOD_TPU_SERVICE is set). Idempotent."""
    global _GATE
    with _GATE_LOCK:
        if _GATE is None:
            _GATE = ServiceGate(cfg.service_port, secret)
            hlog.info(f"service gate listening on port {_GATE.port}",
                      rank=0)
    return _GATE


def service_gate() -> Optional[ServiceGate]:
    return _GATE


def stop_service_gate() -> None:
    global _GATE
    with _GATE_LOCK:
        gate, _GATE = _GATE, None
    if gate is not None:
        gate.close()


def publish_snapshot(params: Dict, version: Optional[int] = None
                     ) -> int:
    """Publish the current parameter snapshot to attached service-mode
    replica groups (rank 0 of a --service fleet; raises elsewhere)."""
    gate = _GATE
    if gate is None:
        raise ValueError(
            "no service gate is running — launch with hvdtpurun "
            "--service (HOROVOD_TPU_SERVICE=1) and publish from "
            "rank 0")
    return gate.publish(params, version)


class AttachedReplica:
    """A service-mode job's handle on the warm fleet: one replica of
    an attached group. ``fetch_snapshot`` pulls the latest published
    parameters — the group ROOT pulls from the gate, every replica
    relays to its binary-tree children, so the fleet pays one send per
    group regardless of group size."""

    def __init__(self, addr: str, port: int, tenant: str,
                 replica: int, group: int, secret: bytes = b"",
                 timeout: float = 30.0):
        self.tenant = tenant
        self.replica = int(replica)
        self.group = max(1, int(group))
        self._secret = secret
        # Fanout listener first: the lease's member table must carry a
        # live endpoint before the gate hands it to our parent.
        self._listener = network.listen(0)
        self._listener.settimeout(timeout)
        self.fanout_port = self._listener.getsockname()[1]
        self._ch = network.connect(addr, port, secret, timeout=timeout,
                                   retry_deadline=timeout)
        self._ch.send(wire.serialize_tenant_attach(
            wire.TENANT_ATTACH, 0, 0, tenant, self.replica,
            self.group, "127.0.0.1", self.fanout_port), SERVICE_TAG)
        try:
            tag, payload = self._ch.recv()
        except ConnectionError as e:
            # The gate rejects a bad first frame by closing — the
            # usual cause is a secret mismatch (the service plane
            # shares the fleet's HMAC auth boundary).
            raise ConnectionError(
                f"service gate at {addr}:{port} closed the attach "
                f"handshake: {e} — does this job present the fleet's "
                f"HOROVOD_SECRET_KEY?") from e
        if tag != SERVICE_TAG:
            raise ConnectionError(f"unexpected tag {tag}")
        lease = wire.parse_tenant_lease(payload)
        if lease["kind"] != wire.TENANT_LEASE:
            raise ConnectionError(
                f"attach refused (kind {lease['kind']})")
        self.lease = lease["lease"]
        self.generation = lease["gen"]
        self.members = lease["members"]

    def _children(self) -> List[int]:
        kids = [2 * self.replica + 1, 2 * self.replica + 2]
        return [k for k in kids if k < len(self.members)]

    def fetch_snapshot(self, min_version: int = 0,
                       timeout: float = 60.0):
        """-> (version, {name: numpy array}). Root: request + receive
        from the gate; children: receive the relayed frame from their
        tree parent. Every replica relays onward — children connect
        FIRST so the native cut-through (hvd_relay_frame, the same
        chunked relay the hierarchical data plane rides) can stream
        each chunk downstream while it is still arriving; deep trees
        then pay one frame time plus depth chunk times instead of
        depth frame times. Wire byte-identical to the classic
        recv-then-send leg, which remains the fallback."""
        kid_chs: List = []
        try:
            for kid in self._children():
                host, port = self.members[kid]
                kid_chs.append(network.connect(
                    host, port, self._secret, timeout=timeout,
                    retry_deadline=timeout))
            src_owned = None
            if self.replica == 0:
                self._ch.send(wire.serialize_tenant_attach(
                    wire.TENANT_SNAPSHOT_REQ, 0, 0, self.tenant,
                    int(min_version), self.group, "", 0), SERVICE_TAG)
                src = self._ch
            else:
                self._listener.settimeout(timeout)
                sock, _ = self._listener.accept()
                sock.settimeout(timeout)
                src_owned = network.Channel(sock, self._secret)
                src = src_owned
            try:
                frame = self._relay_recv(src, kid_chs, timeout)
                if frame is None:  # classic store-and-forward
                    tag, frame = src.recv()
                    if tag != SERVICE_TAG:
                        raise ConnectionError(f"unexpected tag {tag}")
                    for kid_ch in kid_chs:
                        kid_ch.send(frame, SERVICE_TAG)
            finally:
                if src_owned is not None:
                    src_owned.close()
        finally:
            for kid_ch in kid_chs:
                try:
                    kid_ch.close()
                except OSError:
                    pass
        return wire.parse_tenant_snapshot(frame)

    # Cut-through chunk size — matches the hierarchical data plane's
    # (common/controller.py _RELAY_CHUNK_BYTES rationale).
    _RELAY_CHUNK_BYTES = 256 * 1024
    _RELAY_BUF_BYTES = 1 << 20

    def _relay_recv(self, src, kid_chs, timeout: float):
        """One SERVICE_TAG frame from ``src`` streamed to the
        pre-connected children chunk-by-chunk (hvd_relay_frame).
        Returns the payload bytes, or None when the native relay
        cannot run (no lib / stale pre-reactor .so) — the caller then
        takes the classic leg. A non-SERVICE_TAG frame is a protocol
        error on this plane, relayed or not."""
        from horovod_tpu import native as _native
        lib = _native.get()
        if lib is None or not hasattr(lib, "hvd_relay_frame"):
            return None
        import ctypes as ct
        try:
            src_fd = src.sock.fileno()
            fds = [ch.sock.fileno() for ch in kid_chs]
        except OSError:
            return None
        kid_fds = (ct.c_int * max(1, len(fds)))(*(fds or [-1]))
        buf = bytearray(self._RELAY_BUF_BYTES)
        win = (ct.c_uint8 * len(buf)).from_buffer(buf)
        secret = self._secret or b""
        sbuf = (ct.c_uint8 * max(1, len(secret))).from_buffer_copy(
            secret or b"\x00")
        out_len = ct.c_int64(0)
        out_tag = ct.c_uint8(0)
        spill = ct.POINTER(ct.c_uint8)()
        rc = lib.hvd_relay_frame(
            src_fd, kid_fds, len(fds), SERVICE_TAG,
            ct.addressof(win), len(buf), sbuf, len(secret),
            None, 0, self._RELAY_CHUNK_BYTES,
            max(1, int(timeout * 1000)), -1,
            ct.byref(out_len), ct.byref(out_tag), ct.byref(spill))
        if rc == 2:
            # Deviation (absorbed, not relayed): free the bounce and
            # fail exactly like the classic leg's tag check.
            if spill:
                lib.hvd_free(spill)
            raise ConnectionError(f"unexpected tag {out_tag.value}")
        if rc == 1:  # relayed, payload spilled past the buffer
            payload = ct.string_at(spill, out_len.value)
            lib.hvd_free(spill)
            return payload
        if rc == 0:
            return bytes(buf[:out_len.value])
        raise ConnectionError(
            f"snapshot relay failed: errno {-rc}")

    def detach(self) -> None:
        """Leave the service plane; the fleet never notices beyond the
        gate's bookkeeping (no re-rendezvous, no world event)."""
        try:
            self._ch.send(wire.serialize_tenant_attach(
                wire.TENANT_DETACH, 0, 0, self.tenant, self.replica,
                self.group, "", 0), SERVICE_TAG)
            tag, payload = self._ch.recv()
            if tag != SERVICE_TAG or not payload \
                    or payload[0] != wire.TENANT_ACK:
                raise ConnectionError("detach not acknowledged")
        finally:
            try:
                self._ch.close()
            except OSError:
                pass
            try:
                self._listener.close()
            except OSError:
                pass


def attach(addr: str, port: int, tenant: str, replica: int = 0,
           group: int = 1, secret: bytes = b"",
           timeout: float = 30.0) -> AttachedReplica:
    """Attach a service-mode job replica to a warm --service fleet."""
    return AttachedReplica(addr, port, tenant, replica, group,
                           secret=secret, timeout=timeout)
