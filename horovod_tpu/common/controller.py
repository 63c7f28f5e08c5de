"""Control-plane controllers: how ranks exchange Request/Response lists.

The reference's control plane is MPI on a private duplicated communicator:
each cycle, workers ``MPI_Gather`` + ``MPI_Gatherv`` their serialized
``RequestList`` to rank 0 and receive the fused ``ResponseList`` via
``MPI_Bcast`` (reference: horovod/common/operations.cc:1044-1065 and
1281-1302). A TPU pod has no MPI; this module supplies the same three
primitives — gather-to-coordinator, broadcast-from-coordinator, identity
metadata — over persistent HMAC'd TCP connections, plus a trivial
in-process controller for size-1 worlds.

The handshake also computes local/cross topology: ranks are grouped by
hostname exactly like the reference's ``MPI_Comm_split_type(SHARED)`` +
``MPI_Comm_split(local_rank)`` construction
(reference: operations.cc:729-764, run/common/util/host_hash.py).
"""

from __future__ import annotations

import errno
import ipaddress
import json
import os
import select
import socket
import struct
import time
from typing import Dict, List, Optional

from horovod_tpu.common import config as hconfig
from horovod_tpu.common import heartbeat
from horovod_tpu.common import logging as hlog
from horovod_tpu.common import network
from horovod_tpu.common import wire
from horovod_tpu.common.status import WorldAbortedError, world_abort_message

def _my_hostname() -> str:
    """Hostname used for local/cross topology grouping. The
    HOROVOD_HOSTNAME override serves containerized ranks whose kernel
    hostname is meaningless, and lets tests force a multi-host shape
    on one machine (reference analog: host_hash's override-free
    hostname grouping, run/common/util/host_hash.py)."""
    return hconfig.env_str("HOROVOD_HOSTNAME") or socket.gethostname()


def _local_root_addr() -> str:
    """Address same-host leaf ranks use to reach their local root's
    listener (hierarchical control plane). Loopback is right whenever
    the host's ranks share a network namespace; per-rank containers
    that share only HOROVOD_HOSTNAME set HOROVOD_TPU_LOCAL_ROOT_ADDR
    to a mutually reachable address (the root binds it too)."""
    return hconfig.env_str("HOROVOD_TPU_LOCAL_ROOT_ADDR", "127.0.0.1")


def host_groups(hostnames: List[str]):
    """Group ranks by hostname in first-seen host order — THE canonical
    grouping every control-plane participant must agree on (topology,
    coordinator aggregation, local-root membership all derive from this
    one function; reference: operations.cc:729-764).

    Returns (hosts, members) with ``hosts`` the distinct hostnames in
    first-appearance order and ``members[i]`` the ascending global
    ranks on ``hosts[i]``."""
    hosts: List[str] = []
    for h in hostnames:
        if h not in hosts:
            hosts.append(h)
    members = [[r for r in range(len(hostnames)) if hostnames[r] == h]
               for h in hosts]
    return hosts, members


# Frame tags on the controller channel.
TAG_HANDSHAKE = 1
TAG_REQUESTS = 2    # worker -> coordinator: serialized RequestList
TAG_RESPONSES = 3   # coordinator -> worker: serialized ResponseList
TAG_DATA = 4        # data-plane payload (socket fallback backend)
TAG_PING = 5        # downward liveness beacon (heartbeat.encode_ping)
TAG_ABORT = 6       # world abort notice (heartbeat.encode_abort)
TAG_METRICS = 7     # upward metrics snapshot (wire.*_metrics_frame) —
                    # out-of-band like PING: absorbed wherever a
                    # control frame is awaited, never negotiated
TAG_TRACE = 8       # upward trace-span batch (wire.*_trace_frame,
                    # common/trace.py) — out-of-band like METRICS;
                    # carries the worker half of the clock-sync echo


def _dead_peers(channels: Dict[int, "network.Channel"]) -> List[int]:
    """Ranks whose channel socket is dead (hung up, errored, or
    orderly-closed), probed without blocking. Called only on failure
    paths, to turn an anonymous transport error from a fan-out
    primitive into a named origin rank."""
    dead: List[int] = []
    for r, ch in channels.items():
        try:
            fd = ch.sock.fileno()
        except OSError:
            fd = -1
        if fd < 0:
            # Locally closed (e.g. an injected sever): dead by
            # definition, and poll.register would raise on it.
            dead.append(r)
            continue
        try:
            p = select.poll()
            p.register(fd, select.POLLIN)
            events = p.poll(0)
            if not events:
                continue
            mask = events[0][1]
            if mask & (select.POLLHUP | select.POLLERR | select.POLLNVAL):
                dead.append(r)
            elif mask & select.POLLIN:
                # Readable could be a buffered frame OR an orderly
                # close; peek distinguishes without consuming.
                if ch.sock.recv(1, socket.MSG_PEEK) == b"":
                    dead.append(r)
        except OSError:
            dead.append(r)
    return dead


def _abort_error(origin: int, cause: str,
                 resolved: bool = False) -> WorldAbortedError:
    """``resolved=True`` marks an AUTHORITATIVE notice decoded off the
    wire: the runtime's failure handler then commits the origin as-is
    instead of re-draining the control plane for a better one."""
    err = WorldAbortedError(world_abort_message(origin, cause),
                            origin_rank=origin, cause=cause)
    err.resolved = resolved
    return err


def _drain_abort(channels: Dict[int, "network.Channel"],
                 grace_s: float) -> Optional[tuple]:
    """Sweep the control channels for a queued (or just-arriving,
    within ``grace_s``) TAG_ABORT notice → (origin, cause), else None.

    A locally inferred transport blame can race the authoritative
    notice from the rank that actually DETECTED the failure: its
    teardown closes channels, and to peers that close is a second,
    misattributable failure (e.g. a ring survivor names its dead
    neighbor and collapses; this rank only sees the survivor's close).
    Failure path only — never runs in a healthy world. Non-abort
    frames found in the sweep are discarded; the world is already
    dead, nothing will negotiate them."""
    deadline = time.monotonic() + grace_s
    while True:
        for ch in channels.values():
            # Bypass the channel's liveness slicing: a 50 ms cap per
            # read keeps the sweep prompt even over partial frames
            # flushed by a dying peer.
            prev_hb, ch._hb = ch._hb, None
            try:
                prev_to = ch.sock.gettimeout()
                ch.sock.settimeout(0.05)
                try:
                    p = select.poll()
                    p.register(ch.sock.fileno(), select.POLLIN)
                    while p.poll(0):
                        tag, data = ch.recv()
                        if tag == TAG_ABORT:
                            return heartbeat.decode_abort(data)
                finally:
                    ch.sock.settimeout(prev_to)
            except (OSError, ValueError):
                pass  # dead/garbled channel: nothing to learn here
            finally:
                ch._hb = prev_hb
        if time.monotonic() >= deadline:
            return None
        time.sleep(0.02)


def _maybe_ping(ctl, channels: Dict[int, "network.Channel"],
                sender_rank: int) -> None:
    """Shared PING fan-out for both tree tiers (coordinator → owners,
    local root → leaves): rate-limited to the controller's configured
    interval (idle slices can tick faster — see _NativeFanout), send
    failures swallowed (the recv/abort paths own the reporting)."""
    now = time.monotonic()
    if now - ctl._last_ping < _ping_interval(ctl._hb_timeout,
                                            ctl._hb_interval):
        return
    ctl._last_ping = now
    ctl._ping_seq += 1
    if sender_rank == 0 and not getattr(ctl, "_world_id", 0):
        # Clock-sync t1: the coordinator clock is the world's
        # reference frame, so only rank 0's beacons are recorded
        # (common/trace.py ClockSync; local-root beacons carry their
        # own clocks and would poison the table — and so would a
        # TENANT sub-world's coordinator, whose ping sequence is a
        # different stream than the default world's).
        from horovod_tpu.common import trace as htrace
        htrace.clock().ping_sent(ctl._ping_seq, now)
    payload = heartbeat.encode_ping(sender_rank, ctl._ping_seq)
    for ch in channels.values():
        try:
            ch.send(payload, TAG_PING)
        except OSError:
            pass


def _hb_normalized(timeout_s: float, interval_s: float) -> tuple:
    """(timeout_s, interval_s) with the interval clamped into
    (0, timeout/2] — same normalization Channel.arm applies, so the
    native fanout's slice loop can't busy-poll on interval<=0,
    overshoot the deadline by a whole oversized interval, or tick
    on_idle (the PING beacon) fewer than twice per peer deadline
    window."""
    half = timeout_s / 2.0
    interval_s = min(interval_s, half) if interval_s > 0 else half
    return timeout_s, interval_s


def _ping_interval(timeout_s: float, interval_s: float) -> float:
    """The PING send gate must beacon at least twice per deadline
    window regardless of the configured interval — gating on a raw
    interval >= the timeout would starve every waiting receiver of
    proof-of-life and falsely abort a healthy world."""
    half = timeout_s / 2.0
    return min(interval_s, half) if interval_s > 0 else half


_PACK_COUNT = struct.Struct("<I")
_PACK_LEN = struct.Struct("<Q")


def pack_frames(frames: List[bytes]) -> bytes:
    """Concatenate several per-rank frames into one aggregate payload
    (hierarchical control plane: a host's local root forwards ONE frame
    carrying all its ranks' messages — the control-plane rendering of
    the reference's LOCAL-then-CROSS communicator split,
    reference: horovod/common/operations.cc:729-764)."""
    parts = [_PACK_COUNT.pack(len(frames))]
    for f in frames:
        parts.append(_PACK_LEN.pack(len(f)))
        parts.append(bytes(f) if not isinstance(f, (bytes, bytearray))
                     else f)
    return b"".join(parts)


def unpack_frames(blob: bytes) -> List[bytes]:
    """Inverse of :func:`pack_frames`. An aggregate truncated
    mid-header raises ConnectionError like every other malformed
    control frame — the relay error handling (and the fail-fast blame
    machinery behind it) is written around the ConnectionError family,
    and a raw ``struct.error`` would escape it."""
    try:
        (n,) = _PACK_COUNT.unpack_from(blob, 0)
        off = _PACK_COUNT.size
        out: List[bytes] = []
        for _ in range(n):
            (ln,) = _PACK_LEN.unpack_from(blob, off)
            off += _PACK_LEN.size
            if off + ln > len(blob):
                raise ConnectionError(
                    f"aggregate frame truncated: slot of {ln} bytes "
                    f"at offset {off} overruns {len(blob)}-byte blob")
            out.append(bytes(blob[off:off + ln]))
            off += ln
    except struct.error as e:
        raise ConnectionError(
            f"aggregate frame truncated mid-header: {e}") from e
    if off != len(blob):
        raise ConnectionError(
            f"aggregate frame has {len(blob) - off} trailing bytes")
    return out


def _dialable_leaf_ip(ip: str) -> bool:
    """True when a leaf's observed connect address is worth recording
    as its dialable override. Loopback means shared-netns (the root
    channel's IP answers for the leaf) — and that includes IPv6
    ``::1``, which a prefix test on ``127.`` would wrongly record as
    a dialable address. Unparseable strings stay excluded."""
    try:
        return not ipaddress.ip_address(ip).is_loopback
    except ValueError:
        return False


def _accept_handshakes(server, secret: bytes, deadline: float,
                       timeout_msg, validate):
    """Shared hardened accept loop (coordinator startup and local-root
    leaf rendezvous): accept, handshake, validate; a stray probe, a
    garbage frame, or a peer dying mid-handshake is rejected without
    aborting startup. ``validate(hello) -> rank`` raises
    ConnectionError (or Key/Value/TypeError) to reject; ``timeout_msg``
    is a callable so the error reflects progress at expiry. Yields
    (rank, hello, channel) per accepted peer, forever — the caller
    stops iterating when it has everyone."""
    server.settimeout(1.0)
    while True:
        if time.monotonic() > deadline:
            raise TimeoutError(timeout_msg())
        try:
            sock, _ = server.accept()
        except socket.timeout:
            continue
        try:
            sock.settimeout(5.0)
            ch = network.Channel(sock, secret)
            tag, payload = ch.recv()
            if tag != TAG_HANDSHAKE:
                raise ConnectionError(f"unexpected tag {tag}")
            hello = json.loads(payload.decode())
            r = validate(hello)
        except (ConnectionError, socket.timeout, ValueError,
                KeyError, TypeError, UnicodeDecodeError) as e:
            hlog.warning(f"rejected connection during startup: {e}")
            try:
                sock.close()
            except OSError:
                pass
            continue
        sock.settimeout(None)
        yield r, hello, ch


class _NativeFanout:
    """poll(2)-based frame gather/broadcast/scatter over a fixed set of
    peer channels through the native core (native/hvdtpu.cc, GIL
    released) — the per-cycle hot path shared by the coordinator (its
    worker channels) and by hierarchical local roots (their leaf
    children). :meth:`create` returns None when the native library is
    unavailable or there are no peers; callers then fall back to the
    per-channel Python loops."""

    def __init__(self, lib, ctypes_mod, channels: Dict[int, "network.Channel"],
                 secret: bytes, hb=None, on_metrics=None,
                 on_trace=None):
        self._lib = lib
        self._ct = ctypes_mod
        # callable(rank, payload) fired when a TAG_METRICS frame
        # arrives in a gather slice (the sender stays pending — its
        # real cycle frame is still owed). None drops such frames.
        self._on_metrics = on_metrics
        # Same contract for TAG_TRACE frames (common/trace.py).
        self._on_trace = on_trace
        # rank -> CLOCK_MONOTONIC completion stamp of its frame in
        # the LAST gather (from the native arrive array) — read by
        # the coordinator's straggler attribution right after
        # gather() returns; reset per gather.
        self.last_arrivals: Dict[int, float] = {}
        self.ranks = sorted(channels)
        fds = [channels[r].sock.fileno() for r in self.ranks]
        self._fd_list = fds
        self._fds = (ctypes_mod.c_int * len(fds))(*fds)
        self._secret = secret
        self._secret_buf = (ctypes_mod.c_uint8 * max(
            1, len(secret))).from_buffer_copy(secret or b"\x00")
        # (timeout_s, interval_s, on_idle) liveness deadline for gather,
        # or None: the native poll loop then waits in interval slices,
        # firing on_idle (PING fan-out) per idle slice and failing after
        # timeout_s of total silence — same semantics as Channel.arm.
        # The slice is additionally capped at timeout/(2*fan_in): the
        # native call keeps absorbing frames as long as each arrives
        # within one slice and only returns to Python (where on_idle
        # can run) after a fully idle slice, so a trickle of fan_in
        # frames can starve PINGs for at most fan_in*slice <= timeout/2
        # — keeping every waiting peer's own recv deadline safe.
        if hb is not None:
            timeout_s, interval_s, on_idle = hb
            interval_s = min(interval_s,
                             timeout_s / (2.0 * max(1, len(fds))))
            hb = (timeout_s, interval_s, on_idle)
        self._hb = hb
        # Lazily-built ctypes ON_IDLE thunk for the batched reactor
        # (gather_into); cached so the callback object outlives the
        # native calls that fire it.
        self._on_idle_c = None

    @classmethod
    def create(cls, channels, secret: bytes, hb=None, on_metrics=None,
               on_trace=None):
        if not channels:
            return None
        from horovod_tpu import native
        lib = native.get()
        if lib is None:
            return None
        import ctypes
        return cls(lib, ctypes, channels, secret, hb=hb,
                   on_metrics=on_metrics, on_trace=on_trace)

    def _as_u8(self, data):
        """bytes/buffer → ctypes u8 array at memcpy speed (never a
        per-byte Python loop — these sit on the per-cycle hot path).
        Empty-vs-nonempty is decided by len(), never truthiness — a
        numpy payload's __bool__ raises on multi-element arrays."""
        if not len(data):
            return (self._ct.c_uint8 * 1)(0)
        return (self._ct.c_uint8 * len(data)).from_buffer_copy(data)

    def gather(self, expect_tag: int) -> Dict[int, bytes]:
        """One frame from every peer; returns {rank: payload}. With a
        liveness deadline set, the native poll loop runs in interval
        slices: frames already received in a slice are harvested (the
        peers that delivered them are not re-polled), on_idle fires per
        empty slice, and total silence past the timeout raises. A
        TAG_ABORT frame from any peer surfaces as WorldAbortedError."""
        ct = self._ct
        u8p = ct.POINTER(ct.c_uint8)
        out: Dict[int, bytes] = {}
        self.last_arrivals = {}
        pending = list(range(len(self.ranks)))
        if self._hb is None:
            timeout_ms, deadline = -1, None
            timeout_s = interval_s = 0.0
            on_idle = None
        else:
            timeout_s, interval_s, on_idle = self._hb
            timeout_ms = max(1, int(interval_s * 1000))
            deadline = time.monotonic() + timeout_s
        while pending:
            n = len(pending)
            fds = (ct.c_int * n)(*[self._fd_list[i] for i in pending])
            bufs = (u8p * n)()
            lens = (ct.c_int64 * n)()
            tags = (ct.c_uint8 * n)()
            arrive = (ct.c_double * n)()
            still: List[int] = []
            absorbed = False  # out-of-band frames harvested this slice
            try:
                rc = self._lib.hvd_gather_frames(
                    fds, n, self._secret_buf, len(self._secret),
                    bufs, lens, tags, timeout_ms, arrive)
                if rc in (-errno.EAGAIN, -errno.EWOULDBLOCK) \
                        and self._hb is not None:
                    # SO_RCVTIMEO (armed by Channel.arm on these same
                    # fds) fired inside the native blocking read: a
                    # peer stalled MID-FRAME — poll saw readability
                    # but the rest of the frame never arrived within
                    # the heartbeat timeout. The native call doesn't
                    # report WHICH fd timed out, so only blame a rank
                    # when it's unambiguous; otherwise origin=-1
                    # ("unknown rank") with the candidates in the
                    # cause — naming a possibly-healthy peer in the
                    # machine-readable field would be worse.
                    waiting = [self.ranks[i] for i in pending]
                    origin = waiting[0] if len(waiting) == 1 else -1
                    raise _abort_error(
                        origin,
                        f"peer stalled mid-frame (silent for "
                        f"{timeout_s:g}s with a frame outstanding; "
                        f"candidates: rank(s) {waiting}) — presumed "
                        f"dead (heartbeat timeout)")
                if rc != 0 and rc != -errno.ETIMEDOUT:
                    # partial frames may already be malloc'd; the
                    # finally block frees them.
                    raise ConnectionError(
                        f"native gather failed: errno {-rc}")
                for j, i in enumerate(pending):
                    r = self.ranks[i]
                    if not bufs[j]:
                        still.append(i)
                        continue
                    if tags[j] == TAG_ABORT:
                        origin, cause = heartbeat.decode_abort(
                            ct.string_at(bufs[j], lens[j]))
                        raise _abort_error(origin, cause, resolved=True)
                    if tags[j] == TAG_METRICS:
                        # Out-of-band observability frame: absorb it
                        # and keep the sender pending — its real cycle
                        # frame is still owed this gather. It also
                        # counts as proof of life (the frame's arrival
                        # resets the silence window below).
                        if self._on_metrics is not None:
                            self._on_metrics(r, ct.string_at(bufs[j],
                                                             lens[j]))
                        absorbed = True
                        still.append(i)
                        continue
                    if tags[j] == TAG_TRACE:
                        # Same out-of-band contract as METRICS: absorb
                        # (or drop, without a sink) and keep the
                        # sender pending.
                        if self._on_trace is not None:
                            self._on_trace(r, ct.string_at(bufs[j],
                                                           lens[j]))
                        absorbed = True
                        still.append(i)
                        continue
                    if tags[j] != expect_tag:
                        raise ConnectionError(
                            f"expected tag {expect_tag} from rank {r}, "
                            f"got {tags[j]}")
                    out[r] = ct.string_at(bufs[j], lens[j])
                    if arrive[j]:
                        self.last_arrivals[r] = arrive[j]
            finally:
                for j in range(n):
                    if bufs[j]:
                        self._lib.hvd_free(bufs[j])
            if rc == -errno.ETIMEDOUT:
                if on_idle is not None:
                    on_idle()
                if len(still) != len(pending) or absorbed:
                    # some frames landed this slice (cycle frames, or
                    # absorbed out-of-band metrics): the world is
                    # moving — restart the silence window
                    deadline = time.monotonic() + timeout_s
                elif time.monotonic() > deadline:
                    # The gather knows exactly which ranks were silent
                    # — name the first as the abort origin (a merely
                    # wedged peer has a live socket, so the generic
                    # _dead_peers probe upstream would find nothing).
                    waiting = [self.ranks[i] for i in still]
                    raise _abort_error(
                        waiting[0],
                        f"no control frame from rank(s) {waiting} for "
                        f"{timeout_s:g}s — peer presumed dead "
                        f"(heartbeat timeout; raise "
                        f"HOROVOD_HEARTBEAT_TIMEOUT if peers "
                        f"legitimately stall longer)")
            pending = still
        return out

    def send_all(self, payload, tag: int,
                 exclude_rank: Optional[int] = None) -> None:
        ct = self._ct
        if exclude_rank is None:
            fd_list = self._fd_list
            fds, n = self._fds, len(self.ranks)
        else:
            fd_list = [fd for r, fd in zip(self.ranks, self._fds)
                       if r != exclude_rank]
            fds, n = (ct.c_int * len(fd_list))(*fd_list), len(fd_list)
        # Large frames (the coordinator's world blobs) ride the
        # MSG_ZEROCOPY leg when the threshold is armed — pages pinned
        # per send instead of copied into every peer's socket buffer.
        hb = self._hb
        if network.zc_fanout_send(
                self._lib, fd_list, tag, payload, self._secret_buf,
                len(self._secret),
                int(hb[0] * 1000) if hb is not None else -1):
            return
        buf = self._as_u8(payload)
        rc = self._lib.hvd_broadcast_frame(
            fds, n, tag, buf, len(payload), self._secret_buf,
            len(self._secret))
        if rc != 0:
            raise ConnectionError(f"native broadcast failed: errno {-rc}")

    def scatter(self, per_rank: Dict[int, bytes], tag: int) -> None:
        """Send per_rank[r] to each peer r."""
        ct = self._ct
        n = len(self.ranks)
        u8p = ct.POINTER(ct.c_uint8)
        arrs = [self._as_u8(per_rank[r]) for r in self.ranks]
        ptrs = (u8p * n)(*[ct.cast(a, u8p) for a in arrs])
        lens = (ct.c_int64 * n)(
            *[len(per_rank[r]) for r in self.ranks])
        rc = self._lib.hvd_scatter_frames(
            self._fds, n, tag, ptrs, lens, self._secret_buf,
            len(self._secret))
        if rc != 0:
            raise ConnectionError(f"native scatter failed: errno {-rc}")

    # -- batched-submission reactor (docs/performance.md Layer 6) --------
    @property
    def batched_ok(self) -> bool:
        """True when the loaded core exports the batched reactor entry
        (a stale pre-reactor .so simply keeps the sequential path)."""
        return hasattr(self._lib, "hvd_gather_frames_batched")

    def gather_into(self, expect_tag: int, views: Dict[int, object]):
        """One frame per peer straight into caller-owned writable
        buffers via the batched-submission reactor
        (hvd_gather_frames_batched): readiness across every channel is
        discovered in ONE submission per wakeup (io_uring when the
        build and kernel carry it, poll(2) otherwise) and each ready
        frame is read to completion in C with the GIL released — the
        recv-into mirror of :meth:`gather`, minus the per-slice
        malloc/copy round-trips. Out-of-band frames keep the exact
        _recv_data_into semantics: PINGs are absorbed in C,
        METRICS/TRACE bounce out as deviations, are dispatched here
        and the call resumes with the done[] map intact (a peer's
        delivered frame is never re-read). Returns
        ``({rank: length}, {rank: arrive stamp}, [frames-per-wakeup])``.
        """
        ct = self._ct
        from horovod_tpu import native as _native
        n = len(self.ranks)
        order = self.ranks
        mvs = [memoryview(network.as_byte_view(views[r]))
               for r in order]
        # Writable ctypes windows over the caller buffers: kept in a
        # list so the pointers stay live across the (possibly
        # re-entered) native call.
        wins = [(ct.c_uint8 * len(mv)).from_buffer(mv) if len(mv)
                else (ct.c_uint8 * 1)() for mv in mvs]
        bufs = (ct.c_void_p * n)(*[ct.addressof(w) for w in wins])
        caps = (ct.c_int64 * n)(*[len(mv) for mv in mvs])
        lens = (ct.c_int64 * n)()
        done = (ct.c_uint8 * n)()
        arrive = (ct.c_double * n)()
        batch_sizes = (ct.c_int32 * n)()
        nbatches = ct.c_int(0)
        dev_idx = ct.c_int(-1)
        dev_buf = ct.POINTER(ct.c_uint8)()
        dev_len = ct.c_int64(0)
        dev_tag = ct.c_uint8(0)
        skip = (ct.c_uint8 * 1)(TAG_PING)
        if self._hb is None:
            timeout_ms = interval_ms = -1
            timeout_s = 0.0
            on_idle_c = ct.cast(None, _native.ON_IDLE_FUNC)
        else:
            timeout_s, interval_s, on_idle = self._hb
            timeout_ms = max(1, int(timeout_s * 1000))
            interval_ms = max(1, int(interval_s * 1000))
            if self._on_idle_c is None:
                # The ctypes thunk must outlive every native call that
                # may fire it — cache it for the fanout's lifetime.
                self._on_idle_c = _native.ON_IDLE_FUNC(on_idle)
            on_idle_c = self._on_idle_c
        while True:
            rc = self._lib.hvd_gather_frames_batched(
                self._fds, n, self._secret_buf, len(self._secret),
                expect_tag, bufs, caps, lens, skip, 1,
                timeout_ms, interval_ms, on_idle_c, done, arrive,
                batch_sizes, ct.byref(nbatches), ct.byref(dev_idx),
                ct.byref(dev_buf), ct.byref(dev_len),
                ct.byref(dev_tag))
            if rc == 0:
                break
            if rc == 1:
                # Deviation: one authenticated non-PING, non-expected
                # frame was pulled off a peer; dispatch it and resume
                # the batch (the peer stays pending — its real frame
                # is still owed, exactly like _recv_data_into).
                r = order[dev_idx.value]
                tag = dev_tag.value
                if dev_buf:
                    payload = ct.string_at(dev_buf, dev_len.value)
                    self._lib.hvd_free(dev_buf)
                    dev_buf = ct.POINTER(ct.c_uint8)()
                else:
                    payload = b""
                if tag == TAG_METRICS:
                    if self._on_metrics is not None:
                        self._on_metrics(r, payload)
                    continue
                if tag == TAG_TRACE:
                    if self._on_trace is not None:
                        self._on_trace(r, payload)
                    continue
                if tag == TAG_ABORT:
                    origin, cause = heartbeat.decode_abort(payload)
                    raise _abort_error(origin, cause, resolved=True)
                if tag != expect_tag:
                    raise ConnectionError(
                        f"expected tag {expect_tag} from rank {r}, "
                        f"got {tag}")
                # expect_tag but drained to the spill: the frame
                # overflowed its preallocated buffer.
                raise ConnectionError(
                    f"data frame of {dev_len.value} bytes from rank "
                    f"{r} overflows {caps[dev_idx.value]}-byte buffer")
            if rc == -errno.ETIMEDOUT:
                waiting = [order[i] for i in range(n) if not done[i]]
                raise _abort_error(
                    waiting[0] if waiting else -1,
                    f"no control frame from rank(s) {waiting} for "
                    f"{timeout_s:g}s — peer presumed dead (heartbeat "
                    f"timeout; raise HOROVOD_HEARTBEAT_TIMEOUT if "
                    f"peers legitimately stall longer)")
            i = dev_idx.value
            if 0 <= i < n:
                r = order[i]
                raise _abort_error(
                    r, f"control channel to rank {r} failed during "
                       f"the batched gather: errno {-rc}")
            raise ConnectionError(
                f"batched native gather failed: errno {-rc}")
        out = {r: int(lens[i]) for i, r in enumerate(order)}
        self.last_arrivals = {r: arrive[i]
                              for i, r in enumerate(order) if arrive[i]}
        return out, self.last_arrivals, \
            list(batch_sizes[:min(nbatches.value, n)])


def _as_buffer(payload):
    """Normalize a data-plane payload to a flat byte view. Callers may
    pass numpy arrays straight through (zero-copy send path); the
    control plane still deals in bytes."""
    if payload is None:
        return None
    return network.as_byte_view(payload)


# Cut-through chunk size for the hierarchical relay legs
# (hvd_relay_frame): a local root forwards each chunk downstream as it
# arrives, so a leaf's read of chunk i overlaps the root's read of
# chunk i+1 and the per-hop latency approaches max(up, down) instead
# of up + down. 256 KiB keeps the resident window small while still
# amortizing syscalls on multi-MB broadcast payloads.
_RELAY_CHUNK_BYTES = 256 * 1024


def relay_frame_into(up_ch: "network.Channel",
                     child_chs: List["network.Channel"],
                     expect_tag: int, out,
                     timeout_ms: int = -1,
                     interval_ms: int = -1) -> int:
    """Receive one exact-fit frame from ``up_ch`` into ``out`` while
    cut-through forwarding it to every channel in ``child_chs``
    (hvd_relay_frame, the same native leg the hierarchical control
    plane rides). Falls back to recv_into + sendv store-and-forward
    when the native core is absent. Standalone variant of
    ``_relay_up_to_children`` for ephemeral trees (the elastic rejoin
    sync in common/selfop.py) that have Channels but no controller.
    Returns the frame's byte length."""
    mv = memoryview(network.as_byte_view(out))
    from horovod_tpu import native as _native
    lib = _native.get()
    if child_chs and lib is not None and hasattr(lib, "hvd_relay_frame"):
        import ctypes as ct
        win = (ct.c_uint8 * len(mv)).from_buffer(mv) if len(mv) \
            else (ct.c_uint8 * 1)()
        child_fds = (ct.c_int * len(child_chs))(
            *[ch.sock.fileno() for ch in child_chs])
        secret = up_ch.secret or b""
        sbuf = (ct.c_uint8 * max(1, len(secret))).from_buffer_copy(
            secret or b"\x00")
        skip = (ct.c_uint8 * 1)(0xFF)  # no stray tags on a private tree
        out_len = ct.c_int64(0)
        out_tag = ct.c_uint8(0)
        spill = ct.POINTER(ct.c_uint8)()
        rc = lib.hvd_relay_frame(
            up_ch.sock.fileno(), child_fds, len(child_chs), expect_tag,
            ct.addressof(win), len(mv), sbuf, len(secret),
            skip, 0, _RELAY_CHUNK_BYTES, timeout_ms, interval_ms,
            ct.byref(out_len), ct.byref(out_tag), ct.byref(spill))
        if spill:
            lib.hvd_free(spill)
        if rc == 0:
            return out_len.value
        if rc == 1:
            raise ConnectionError(
                f"frame of {out_len.value} bytes from {up_ch.peer} "
                f"overflows {len(mv)}-byte relay buffer")
        if rc == 2:
            raise ConnectionError(
                f"expected tag {expect_tag} from {up_ch.peer}, got "
                f"{out_tag.value}")
        raise ConnectionError(
            f"relay from {up_ch.peer} failed: errno {-rc}")
    tag, n = up_ch.recv_into(mv)
    if tag != expect_tag:
        raise ConnectionError(
            f"expected tag {expect_tag} from {up_ch.peer}, got {tag}")
    for ch in child_chs:
        ch.sendv((mv[:n],), expect_tag)
    return n


class Topology:
    """World/local/cross identity of this process
    (reference: global_state.h:95-118)."""

    __slots__ = ("rank", "size", "local_rank", "local_size",
                 "cross_rank", "cross_size", "is_homogeneous",
                 "local_sizes", "local_roots")

    def __init__(self, rank: int, size: int, local_rank: int = 0,
                 local_size: int = 1, cross_rank: int = 0,
                 cross_size: int = 1, is_homogeneous: bool = True,
                 local_sizes: Optional[List[int]] = None,
                 local_roots: Optional[List[int]] = None):
        self.rank = rank
        self.size = size
        self.local_rank = local_rank
        self.local_size = local_size
        self.cross_rank = cross_rank
        self.cross_size = cross_size
        self.is_homogeneous = is_homogeneous
        self.local_sizes = local_sizes or [local_size]
        # global rank of each host's local_rank-0 process, host order
        self.local_roots = local_roots if local_roots is not None \
            else [0]


def compute_topology(rank: int, hostnames: List[str]) -> Topology:
    """Group ranks by hostname → local/cross communicator shape
    (reference: operations.cc:729-764; homogeneity check 741-757)."""
    size = len(hostnames)
    my_host = hostnames[rank]
    hosts, members = host_groups(hostnames)
    cross_rank = hosts.index(my_host)
    cross_size = len(hosts)
    local_ranks = members[cross_rank]
    local_rank = local_ranks.index(rank)
    local_size = len(local_ranks)
    local_sizes = [len(ms) for ms in members]
    local_roots = [ms[0] for ms in members]
    is_homogeneous = all(s == local_sizes[0] for s in local_sizes)
    return Topology(rank=rank, size=size, local_rank=local_rank,
                    local_size=local_size, cross_rank=cross_rank,
                    cross_size=cross_size, is_homogeneous=is_homogeneous,
                    local_sizes=local_sizes, local_roots=local_roots)


class Controller:
    """Abstract control plane."""

    topology: Topology

    # -- metrics plane (common/metrics.py) -------------------------------
    # Rank-0 sink for METRICS frames arriving off the control tree:
    # callable(owner_rank, payload). Set by the runtime once its
    # WorldAggregator exists; frames arriving earlier are dropped
    # (best-effort totals — the next interval resends them).
    metrics_sink = None
    # -- world trace plane (common/trace.py) -----------------------------
    # Rank-0 sink for TRACE frames: callable(owner_rank, payload),
    # set by the runtime once its WorldTraceWriter exists. TAG_TRACE
    # frames are absorbed on EVERY recv path regardless (dropped
    # without a sink) — a rank with tracing armed must never be able
    # to kill a world whose coordinator has it off.
    trace_sink = None
    # True once attach_trace ran: workers then note coordinator PINGs
    # for the clock-sync echo (an extra decode per rare ping).
    _trace_on = False
    # Rank-0 arrival hook: callable({rank: monotonic stamp}) fired
    # per negotiation gather when the runtime armed straggler
    # attribution (metrics or trace plane on). None keeps the
    # disabled gather free of clock reads.
    _on_arrivals = None

    def attach_trace(self, on_arrivals=None) -> None:
        """Arm trace-plane hooks: worker-side PING noting (clock
        echo), and — on the coordinator — per-gather arrival stamps
        fed to ``on_arrivals``."""
        self._trace_on = True
        if on_arrivals is not None:
            self._on_arrivals = on_arrivals

    def send_trace(self, payload: bytes) -> None:
        """Best-effort upward TRACE frame (workers; a hierarchical
        local root concatenates its host's sections first). Never
        raises — same contract as send_metrics."""
    # Control-plane byte counters + liveness tracking, installed by
    # attach_metrics. The class-attribute defaults keep every
    # unattached (metrics-off) path at a no-op method call.
    # hvdlint: owned-by=main -- installed exactly once by attach_metrics during rendezvous, before any cycle or background thread exists (Thread.start happens-before publishes the counters); never rebound after
    _m_ctrl_rx = None
    _m_ctrl_tx = None
    _metrics_on = False
    # Batched-submission reactor (docs/performance.md Layer 6):
    # enabled by default; the runtime overrides from
    # HOROVOD_TPU_REACTOR so one rank can opt out and the world stays
    # wire byte-identical (the knob only picks this rank's LOCAL recv
    # discipline).
    _reactor = True
    # Frames completed per reactor wakeup (histogram); None until
    # attach_metrics runs — the unattached path records nothing.
    _m_reactor_batch = None

    def attach_metrics(self, registry) -> None:
        """Install control-plane instrumentation from the runtime's
        registry (a no-op registry hands back no-op metrics, keeping
        the disabled path free)."""
        self._m_ctrl_rx = registry.counter(
            'hvd_control_bytes_total{direction="rx"}',
            "control-plane bytes received by this rank")
        self._m_ctrl_tx = registry.counter(
            'hvd_control_bytes_total{direction="tx"}',
            "control-plane bytes sent by this rank")
        # Reactor observability: how many frames each batched wakeup
        # delivered (1s everywhere = the reactor is engaged but the
        # world trickles; missing series = sequential fallback), plus
        # the MSG_ZEROCOPY send counters maintained by the channel
        # layer's module hooks (network.py — a genuinely zero-copy
        # send ticks sends only; sends == copied means the kernel
        # degraded every one to a copy, e.g. loopback).
        self._m_reactor_batch = registry.histogram(
            "hvd_reactor_batch_size",
            "frames completed per batched-reactor wakeup",
            [1, 2, 4, 8, 16, 32])
        network.attach_zerocopy_metrics(
            registry.counter(
                "hvd_zerocopy_sends_total",
                "frames sent with MSG_ZEROCOPY by this rank"),
            registry.counter(
                "hvd_zerocopy_copied_total",
                "MSG_ZEROCOPY completions the kernel degraded to a "
                "plain copy"))
        self._metrics_on = bool(registry.enabled)

    def send_metrics(self, payload: bytes) -> None:
        """Best-effort upward METRICS frame (workers; a hierarchical
        local root folds its host's latest frames in first). Never
        raises — observability must not take the control plane down;
        a dead channel is the cycle path's to report."""

    def peer_heartbeat_ages(self) -> Dict[int, float]:
        """Seconds since the last control frame from each directly
        connected peer (owner channels for the coordinator, upward
        peer + leaves for workers). Only maintained while metrics are
        attached; empty otherwise."""
        return {}

    @property
    def rank(self) -> int:
        return self.topology.rank

    @property
    def size(self) -> int:
        return self.topology.size

    @property
    def is_coordinator(self) -> bool:
        return self.rank == 0

    def gather_requests(self, payload: bytes) -> Optional[List[bytes]]:
        """Coordinator: returns all ranks' serialized RequestLists
        (index = rank), including its own. Workers: send and return None."""
        raise NotImplementedError

    def broadcast_responses(self, payload: Optional[bytes]) -> bytes:
        """Coordinator passes the serialized ResponseList; workers pass
        None. Everyone returns the broadcast bytes."""
        raise NotImplementedError

    # Data-plane helpers for the socket fallback backend -----------------
    def gather_data(self, payload: bytes) -> Optional[List[bytes]]:
        raise NotImplementedError

    def broadcast_data(self, payload: Optional[bytes],
                       root_rank: int = 0) -> bytes:
        raise NotImplementedError

    def scatter_data(self, payloads: Optional[List[bytes]]) -> bytes:
        """Coordinator passes one payload per rank; every rank returns
        its own."""
        raise NotImplementedError

    # -- zero-copy data plane (recv-into variants) -----------------------
    # The *_into primitives move payloads straight between sockets and
    # caller-owned writable buffers (numpy arrays, arena views): no
    # bytes object is materialized on the receive side. Callers must
    # invoke them at the same negotiated response position on every
    # rank, exactly like their bytes-returning counterparts.

    def gather_data_into(self, payload, outs) -> Optional[List[int]]:
        """Data gather with preallocated receive buffers: workers send
        ``payload`` (``outs`` ignored; returns None); the coordinator
        receives rank r's payload straight into ``outs[r]`` (writable;
        ``outs[0]`` untouched — its own payload is already local) and
        returns per-rank byte counts."""
        raise NotImplementedError

    def broadcast_data_into(self, payload, out, root_rank: int = 0) -> int:
        """Broadcast with the receive side landing in ``out``: the
        root sends ``payload`` (its result is its own buffer); every
        other rank fills ``out`` and gets the byte count back."""
        raise NotImplementedError

    def scatter_data_into(self, payloads, out) -> int:
        """Scatter with the receive side landing in ``out``. The
        coordinator passes one payload per rank and only sends (its
        own slice is already local; returns its byte count); workers
        pass None and receive into ``out``."""
        raise NotImplementedError

    # -- native steady cycle (common/steady.py) --------------------------
    def steady_native_ready(self) -> bool:
        """True when this controller can run the one-call native
        steady fused cycle (flat topology tier + native core loaded).
        Stable after startup — the runtime probes once."""
        return False

    def steady_spec_cycle(self, plan, bufs):
        """Run one steady fused cycle natively (see common/steady.py).
        Returns None when unsupported (caller serializes classically),
        ('done', result_segments) on a completed single-round cycle,
        ('frame', payload) on a worker-side deviation (the broadcast
        frame to parse classically), or ('fallback', gathered) on a
        coordinator-side deviation (rank-indexed request frames for
        the classic negotiation). Transport failures raise the same
        WorldAbortedError family as the classic primitives."""
        return None

    def agree(self, local_flag: bool) -> bool:
        """World-wide AND of a per-rank boolean over the data channel.

        Backend-enablement decisions must be identical on every rank or
        the job deadlocks (some ranks inside an XLA collective, others
        in a socket gather). Callers must invoke this at the same point
        of the negotiated response stream on all ranks — which is
        exactly when ``CollectiveBackend.enabled`` runs."""
        gathered = self.gather_data(b"\x01" if local_flag else b"\x00")
        if gathered is not None:  # coordinator
            ok = all(g == b"\x01" for g in gathered)
            return self.broadcast_data(
                b"\x01" if ok else b"\x00") == b"\x01"
        return self.broadcast_data(None) == b"\x01"

    def abort(self, origin_rank: int, cause: str) -> None:
        """Best-effort fan-out of a world ABORT notice to every peer
        this controller talks to directly (coordinator: all owner
        channels; worker: upward + local leaves). Never raises — it
        runs on failure paths where channels may already be dead."""

    def sever_connection(self, target_rank: Optional[int] = None) -> None:
        """Fault injection: abruptly close a control channel (to
        ``target_rank`` when this controller owns several, else the
        upward/all channels), simulating link loss."""

    def drain_abort_notice(self, grace_s: float = 0.0) -> Optional[tuple]:
        """Failure path only: sweep this controller's channels for a
        queued TAG_ABORT → (origin_rank, cause), waiting up to
        ``grace_s`` for one in flight. Lets a rank that inferred a
        blame from an anonymous transport error defer to the
        authoritative notice from the rank that actually detected the
        failure (see _drain_abort)."""
        return None

    def close(self) -> None:
        pass


class LocalController(Controller):
    """Size-1 world: negotiation is immediate."""

    def __init__(self):
        self.topology = Topology(rank=0, size=1)

    def gather_requests(self, payload: bytes) -> Optional[List[bytes]]:
        return [payload]

    def broadcast_responses(self, payload: Optional[bytes]) -> bytes:
        assert payload is not None
        return payload

    def gather_data(self, payload: bytes) -> Optional[List[bytes]]:
        return [payload]

    def broadcast_data(self, payload: Optional[bytes],
                       root_rank: int = 0) -> bytes:
        assert payload is not None
        return payload

    def scatter_data(self, payloads: Optional[List[bytes]]) -> bytes:
        assert payloads is not None and len(payloads) == 1
        return payloads[0]

    def gather_data_into(self, payload, outs) -> Optional[List[int]]:
        return [len(_as_buffer(payload))]

    def broadcast_data_into(self, payload, out, root_rank: int = 0) -> int:
        view = _as_buffer(payload)
        if out is not None and view is not None:
            mv = memoryview(network.as_byte_view(out))
            mv[:len(view)] = view
        return 0 if view is None else len(view)

    def scatter_data_into(self, payloads, out) -> int:
        assert payloads is not None and len(payloads) == 1
        view = _as_buffer(payloads[0])
        if out is not None:
            mv = memoryview(network.as_byte_view(out))
            mv[:len(view)] = view
        return len(view)


class TcpCoordinator(Controller):
    """Rank 0: accepts one persistent connection per worker.

    Per-cycle gather/broadcast hot paths go through the native core
    (native/hvdtpu.cc hvd_gather_frames: one poll(2) loop servicing all
    workers with the GIL released) when the library is available; the
    Python per-channel loop is the fallback."""

    def __init__(self, size: int, port: int = 0, secret: bytes = b"",
                 start_timeout: float = 30.0, listener=None,
                 hierarchical: bool = True,
                 heartbeat_interval: float = 5.0,
                 heartbeat_timeout: float = 30.0,
                 elastic_port: Optional[int] = None,
                 world_id: int = 0,
                 tenant_desc: Optional[dict] = None):
        """``listener`` — an already-bound listening socket to adopt
        instead of binding ``port``. Launch layers that must publish
        the coordinator endpoint BEFORE init (Spark rendezvous,
        hvdtpurun's per-host port reservation) hand the bound socket
        over so there is no close-then-rebind window for another
        process to steal the port.

        ``hierarchical`` — allow per-host control-plane aggregation:
        when the world spans multiple hosts with more than one rank
        each, remote leaf ranks migrate to their host's local root
        after the handshake and the coordinator keeps ONE channel per
        remote host, so per-cycle fan-in is n_hosts + local ranks
        instead of world size (the control-plane analog of the
        reference's hierarchical allreduce communicator split,
        reference: operations.cc:729-764, 822-841)."""
        self._secret = secret
        self._server = listener if listener is not None \
            else network.listen(port)
        self.port = self._server.getsockname()[1]
        self._channels: Dict[int, network.Channel] = {}
        self._hostname = _my_hostname()
        self._size = size
        self._start_timeout = start_timeout
        self._hierarchical = hierarchical
        self._hb_interval = heartbeat_interval
        self._hb_timeout = heartbeat_timeout
        self._ping_seq = 0
        self._last_ping = 0.0
        self.topology = None  # set by accept_workers
        self._fanout: Optional[_NativeFanout] = None
        # channel owner rank -> all ranks that channel represents
        # (ascending; owner first). Flat world: every owner maps to
        # itself. Hierarchical: a remote local root carries its host.
        self._members: Dict[int, List[int]] = {}
        self._owner_of: Dict[int, int] = {}
        self._has_aggregates = False
        # owner rank -> monotonic time of its last control frame
        # (maintained only when metrics are attached; feeds the
        # per-peer heartbeat-age gauges).
        self._last_seen: Dict[int, float] = {}
        # Native steady-cycle state (common/steady.py): per-peer
        # scratch arena + the ctypes PING callback, built lazily on
        # the first steady cycle.
        self._steady_scratch = None
        self._steady_on_idle = None
        # Elastic membership (common/elastic.py): this rank's elastic
        # listener port, exchanged in the handshake so every member
        # learns the full rank -> (host, port) re-rendezvous endpoint
        # map. None = elastic off; populated by accept_workers.
        self._elastic_port = elastic_port
        self.elastic_endpoints: Optional[Dict[int, tuple]] = None
        # Tenancy (common/tenancy.py): the world id every member must
        # present in its handshake — a dialer carrying another world's
        # id (a derived-port collision between two concurrent
        # sub-worlds) is refused at accept instead of poisoning this
        # world's gathers. The coordinator's tenant descriptor
        # (weight/quota knobs) broadcasts with the handshake so
        # scheduling state is world-replicated from cycle 0.
        self._world_id = world_id
        self.tenant_desc = tenant_desc

    def accept_workers(self) -> None:
        deadline = time.monotonic() + self._start_timeout
        hostnames = [None] * self._size
        hostnames[0] = self._hostname

        def _validate(hello):
            r = int(hello["rank"])
            if r <= 0 or r >= self._size or r in self._channels:
                raise ConnectionError(f"bad or duplicate rank {r}")
            wid = int(hello.get("world_id", 0))
            if wid != self._world_id:
                raise ConnectionError(
                    f"rank {r} dialed with world id {wid:#010x}; this "
                    f"coordinator serves world {self._world_id:#010x} "
                    f"— two sub-worlds are sharing a port")
            hello["hostname"]  # reject (KeyError) if absent
            return r

        accepts = _accept_handshakes(
            self._server, self._secret, deadline,
            lambda: (f"Only {len(self._channels) + 1}/{self._size} ranks "
                     f"connected within start timeout; increase "
                     f"HOROVOD_START_TIMEOUT if startup is slow."),
            _validate)
        elastic_ports: Dict[int, int] = {}
        peer_ips: Dict[int, str] = {}
        while len(self._channels) < self._size - 1:
            r, hello, ch = next(accepts)
            hostnames[r] = hello["hostname"]
            ch.peer = f"rank {r} ({ch.peer})"
            # hvdlint: owned-by=main -- rendezvous runs before the world's cycle threads start (Thread.start happens-before publishes it); elastic rebuilds a fresh coordinator
            self._channels[r] = ch
            if hello.get("elastic_port") is not None:
                elastic_ports[r] = int(hello["elastic_port"])
                try:
                    peer_ips[r] = ch.sock.getpeername()[0]
                except OSError:
                    peer_ips[r] = "127.0.0.1"
        # Broadcast the full hostname list so every rank derives the same
        # topology (reference: operations.cc:729-764).
        self.topology = compute_topology(0, hostnames)
        topo = self.topology
        # Hierarchy pays only when remote hosts have leaf ranks to fold
        # behind their local root.
        remote_leaves = (self._size - topo.local_sizes[0]
                         - (topo.cross_size - 1))
        hier = (self._hierarchical and topo.cross_size > 1
                and remote_leaves > 0)
        handshake = {"hostnames": hostnames, "hier": hier}
        if self._world_id:
            handshake["world_id"] = self._world_id
        if self.tenant_desc is not None:
            handshake["tenant"] = self.tenant_desc
        # Elastic endpoint map: only meaningful when EVERY member runs
        # elastic mode (the knob must be world-uniform, like the cache
        # knobs); a partial map would leave some ranks unreachable at
        # re-rendezvous, so it is withheld entirely.
        if self._elastic_port is not None \
                and len(elastic_ports) == self._size - 1:
            handshake["elastic"] = {
                "coord_port": self._elastic_port,
                "ports": {str(r): p for r, p in elastic_ports.items()},
                "ips": {str(r): ip for r, ip in peer_ips.items()},
            }
            self.elastic_endpoints = {0: ("", self._elastic_port)}
            for r, p in elastic_ports.items():
                self.elastic_endpoints[r] = (peer_ips[r], p)
        elif self._elastic_port is not None:
            hlog.warning(
                "HOROVOD_ELASTIC is not set on every rank; elastic "
                "re-rendezvous disabled for this world", rank=0)
        blob = json.dumps(handshake).encode()
        for r, ch in self._channels.items():
            ch.send(blob, TAG_HANDSHAKE)
        self._members = {r: [r] for r in self._channels}
        self._peer_ip_override: Dict[int, str] = {}
        if hier:
            self._setup_hierarchy(hostnames, deadline)
        self._owner_of = {}
        for owner, ms in self._members.items():
            for m in ms:
                self._owner_of[m] = owner
        self._has_aggregates = any(
            len(ms) > 1 for ms in self._members.values())
        hb = None
        if self._hb_timeout and self._hb_timeout > 0:
            hb = _hb_normalized(self._hb_timeout, self._hb_interval) \
                + (self._ping_peers,)
            for ch in self._channels.values():
                ch.arm(self._hb_timeout, self._hb_interval,
                       on_idle=self._ping_peers)
        if self._size > 1:
            self._fanout = _NativeFanout.create(self._channels,
                                                self._secret, hb=hb,
                                                on_metrics=self._on_metrics,
                                                on_trace=self._on_trace_frame)
        hlog.debug(f"coordinator up: {self._size} ranks, "
                   f"{self.topology.cross_size} hosts, "
                   f"fan-in {len(self._channels)}", rank=0)

    def _setup_hierarchy(self, hostnames: List[str],
                         deadline: float) -> None:
        """Collapse each remote host's ranks behind its local root:
        gather root listener ports, hand the port map to remote leaves,
        and drop their direct channels. After this the coordinator's
        per-cycle fan-in is (host-0 local ranks) + (remote hosts).
        Every blocking recv here is bounded by the same start deadline
        that bounds accept_workers — a root dying mid-setup must fail
        the job fast, not hang it."""
        _, host_members = host_groups(hostnames)
        root_ports: Dict[str, int] = {}
        for cross, members in enumerate(host_members[1:], start=1):
            if len(members) == 1:
                continue  # solo host: stays a direct channel
            root = members[0]
            tag, data = self._recv_by(self._channels[root], deadline,
                                      f"port report from root {root}")
            if tag != TAG_HANDSHAKE:
                raise ConnectionError(
                    f"expected root port report from rank {root}, got "
                    f"tag {tag}")
            root_ports[str(cross)] = int(
                json.loads(data.decode())["port"])
        map_blob = json.dumps({"roots": root_ports}).encode()
        agg_roots: List[int] = []
        for members in host_members[1:]:
            if len(members) == 1:
                continue
            for leaf in members[1:]:
                ch = self._channels.pop(leaf)
                self._members.pop(leaf)
                ch.send(map_blob, TAG_HANDSHAKE)
                ch.close()
            self._members[members[0]] = members
            agg_roots.append(members[0])
        # Each root reports the IPs it observed its leaves connect
        # from, once they all arrive. A non-loopback leaf IP (per-rank
        # containers, HOROVOD_TPU_LOCAL_ROOT_ADDR set) overrides
        # worker_peer_ip for that rank so ring rendezvous dials the
        # leaf's own address; loopback means shared-netns, where the
        # root channel's IP is the host's reachable address for all
        # its ranks.
        for root in agg_roots:
            tag, data = self._recv_by(self._channels[root], deadline,
                                      f"leaf-IP report from root {root}")
            if tag != TAG_HANDSHAKE:
                raise ConnectionError(
                    f"expected leaf-IP report from rank {root}, got "
                    f"tag {tag}")
            for r, ip in json.loads(data.decode())["leaf_ips"].items():
                if _dialable_leaf_ip(ip):
                    self._peer_ip_override[int(r)] = ip

    @staticmethod
    def _recv_by(ch: network.Channel, deadline: float,
                 what: str) -> tuple:
        """recv() bounded by the startup deadline."""
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise TimeoutError(
                f"start timeout expired waiting for {what}; increase "
                f"HOROVOD_START_TIMEOUT if startup is slow.")
        ch.sock.settimeout(remaining)
        try:
            return ch.recv()
        except socket.timeout:
            raise TimeoutError(
                f"start timeout expired waiting for {what}; increase "
                f"HOROVOD_START_TIMEOUT if startup is slow.")
        finally:
            ch.sock.settimeout(None)

    def _expand(self, out: List[bytes],
                allow_combined: bool = False) -> List[bytes]:
        """Unpack aggregate frames from local roots into per-rank
        slots (gather direction). ``allow_combined`` (control-plane
        request gathers only): a local root that AND-reduced its whole
        host's cache bitmasks forwards ONE CACHED_AGG cycle frame
        instead of a per-rank pack — it stays in the owner's slot and
        the members' slots are left empty, since the fold already
        accounts for every rank behind it. Request-tag packs that
        could NOT be folded arrive under an explicit PACKED envelope
        byte (a raw pack's leading u32 count is ambiguous: 2 ranks
        pack to a leading 0x02 — the CACHED_AGG kind byte)."""
        if not self._has_aggregates:
            return out
        for owner, members in self._members.items():
            if len(members) == 1:
                continue
            blob = out[owner]
            if allow_combined:
                # A tenant world's folded aggregate leads with the
                # world-id envelope; the CACHED_AGG kind byte then
                # sits right after it (wire.read_world).
                kind_off = 5 if blob[:1] == wire.TENANT_PREFIX else 0
                if blob[kind_off:kind_off + 1] == \
                        wire.CACHED_AGG_PREFIX:
                    for m in members:
                        if m != owner:
                            out[m] = b""
                    continue
                if blob[:1] != wire.PACKED_PREFIX:
                    raise ConnectionError(
                        f"request aggregate from rank {owner} has "
                        f"kind {blob[0] if blob else None}; expected "
                        f"a folded CACHED_AGG frame or a PACKED "
                        f"envelope")
                blob = blob[1:]
            frames = unpack_frames(blob)
            if len(frames) != len(members):
                raise ConnectionError(
                    f"aggregate from rank {owner} carried "
                    f"{len(frames)} frames for {len(members)} ranks")
            for m, f in zip(members, frames):
                out[m] = f
        return out

    def _ping_peers(self) -> None:
        """Fired per idle gather slice: tell every worker the world is
        alive (the straggler the gather waits on is silent TO THEM
        too — without this, their recv deadlines would false-fire on
        a merely slow peer)."""
        _maybe_ping(self, self._channels, 0)

    def _on_metrics(self, r: int, payload: bytes) -> None:
        """A METRICS frame from owner channel ``r`` (native gather or
        the Python recv loop): record liveness and hand it to the
        runtime's aggregator when one is attached."""
        if self._metrics_on:
            self._last_seen[r] = time.monotonic()
        sink = self.metrics_sink
        if sink is not None:
            sink(r, payload)

    def _on_trace_frame(self, r: int, payload: bytes) -> None:
        """A TRACE frame from owner channel ``r``: liveness, then the
        runtime's WorldTraceWriter (dropped without one — a worker
        with tracing armed must never hurt an unarmed coordinator)."""
        if self._metrics_on:
            self._last_seen[r] = time.monotonic()
        sink = self.trace_sink
        if sink is not None:
            sink(r, payload)

    def peer_heartbeat_ages(self) -> Dict[int, float]:
        # list() snapshots the dict atomically under the GIL — the
        # background loop inserts new peers while user threads
        # (hvd.metrics()) iterate.
        now = time.monotonic()
        return {r: now - t for r, t in list(self._last_seen.items())}

    def _recv_ctrl(self, r: int, ch: network.Channel,
                   expect_tag: int) -> bytes:
        """One control frame from rank ``r``'s channel: PINGs are
        liveness-only and skipped, ABORT raises the structured error,
        transport failures are named after the peer."""
        while True:
            try:
                tag, data = ch.recv()
            except WorldAbortedError:
                raise
            except (ConnectionError, OSError) as e:
                raise _abort_error(
                    r, f"control channel to {ch.peer} failed: {e}") \
                    from e
            if tag == TAG_PING:
                continue
            if tag == TAG_METRICS:
                self._on_metrics(r, data)
                continue
            if tag == TAG_TRACE:
                self._on_trace_frame(r, data)
                continue
            if tag == TAG_ABORT:
                origin, cause = heartbeat.decode_abort(data)
                raise _abort_error(origin, cause, resolved=True)
            if tag != expect_tag:
                raise ConnectionError(
                    f"expected tag {expect_tag} from rank {r}, "
                    f"got {tag}")
            if self._metrics_on:
                self._last_seen[r] = time.monotonic()
            return data

    def _raise_transport(self, e: Exception) -> None:
        """Turn an anonymous transport error from a fan-out primitive
        into a WorldAbortedError naming the dead peer when one can be
        identified (native gather and broadcast errors carry no rank)."""
        dead = _dead_peers(self._channels)
        if dead:
            raise _abort_error(
                dead[0], f"connection to rank {dead[0]} lost: {e}") \
                from e
        raise _abort_error(0, f"coordinator transport failure: {e}") \
            from e

    def _gather_frames(self, payload, expect_tag: int) -> List[bytes]:
        """One frame per channel (native poll loop when available),
        rank-indexed with this rank's own payload at 0, aggregate
        frames expanded to their member ranks. Combined (AND-reduced)
        cache bitmask aggregates are only meaningful on the request
        tag — a data-plane payload may begin with any byte."""
        out: List[bytes] = [b""] * self._size
        out[0] = payload
        # Straggler attribution (common/trace.py): stamp per-owner
        # arrival times on request gathers when the runtime armed it.
        # Rank 0's own frame "arrives" at gather start — the baseline
        # every lag is measured against. The native fanout stamps at
        # true frame completion (in C); the Python fallback stamps as
        # its sequential recv loop returns, which is best-effort for
        # frames that were already buffered.
        on_arrivals = self._on_arrivals
        track = (expect_tag == TAG_REQUESTS
                 and on_arrivals is not None)
        arrivals: Optional[Dict[int, float]] = \
            {0: time.monotonic()} if track else None
        try:
            if self._fanout is not None:
                gathered = self._fanout.gather(expect_tag)
                if track:
                    arrivals.update(self._fanout.last_arrivals)
                if self._metrics_on:
                    now = time.monotonic()
                    rx = 0
                    for r, data in gathered.items():
                        out[r] = data
                        self._last_seen[r] = now
                        rx += len(data)
                    self._m_ctrl_rx.inc(rx)
                else:
                    for r, data in gathered.items():
                        out[r] = data
            else:
                for r, ch in self._channels.items():
                    out[r] = self._recv_ctrl(r, ch, expect_tag)
                    if track:
                        arrivals[r] = time.monotonic()
                if self._metrics_on:
                    self._m_ctrl_rx.inc(sum(
                        len(out[r]) for r in self._channels))
        except WorldAbortedError:
            raise
        except (ConnectionError, OSError) as e:
            self._raise_transport(e)
        if track:
            on_arrivals(arrivals)
        return self._expand(out,
                            allow_combined=(expect_tag == TAG_REQUESTS))

    def gather_requests(self, payload: bytes) -> Optional[List[bytes]]:
        return self._gather_frames(payload, TAG_REQUESTS)

    def broadcast_responses(self, payload: Optional[bytes]) -> bytes:
        assert payload is not None
        if self._metrics_on:
            self._m_ctrl_tx.inc(len(payload) * len(self._channels))
        try:
            if self._fanout is not None:
                self._fanout.send_all(payload, TAG_RESPONSES)
                return payload
            for ch in self._channels.values():
                ch.send(payload, TAG_RESPONSES)
            return payload
        except (ConnectionError, OSError) as e:
            self._raise_transport(e)

    def gather_data(self, payload: bytes) -> Optional[List[bytes]]:
        return self._gather_frames(_as_buffer(payload), TAG_DATA)

    def broadcast_data(self, payload: Optional[bytes],
                       root_rank: int = 0) -> bytes:
        payload = _as_buffer(payload)
        try:
            if root_rank != 0:
                # Pull the payload up from the root's owning channel,
                # then fan out to every OTHER channel — the owner (the
                # root itself, or the local root relaying for it)
                # already has the bytes and has distributed them on its
                # host, and echoing them back would double its traffic.
                owner = self._owner_of[root_rank]
                payload = self._recv_ctrl(owner, self._channels[owner],
                                          TAG_DATA)
                if self._fanout is not None:
                    self._fanout.send_all(payload, TAG_DATA,
                                          exclude_rank=owner)
                    return payload
                for r, ch in self._channels.items():
                    if r != owner:
                        ch.send(payload, TAG_DATA)
                return payload
            assert payload is not None
            if self._fanout is not None:
                self._fanout.send_all(payload, TAG_DATA)
                return payload
            for ch in self._channels.values():
                ch.send(payload, TAG_DATA)
            return payload
        except WorldAbortedError:
            raise
        except (ConnectionError, OSError) as e:
            self._raise_transport(e)

    def scatter_data(self, payloads: Optional[List[bytes]]) -> bytes:
        assert payloads is not None and len(payloads) == self._size
        per_owner: Dict[int, bytes] = {
            owner: (_as_buffer(payloads[owner]) if len(ms) == 1
                    else pack_frames([_as_buffer(payloads[m])
                                      for m in ms]))
            for owner, ms in self._members.items()}
        try:
            if self._fanout is not None:
                self._fanout.scatter(per_owner, TAG_DATA)
                return payloads[0]
            for r, ch in self._channels.items():
                ch.send(per_owner[r], TAG_DATA)
            return payloads[0]
        except (ConnectionError, OSError) as e:
            self._raise_transport(e)

    def _recv_data_into(self, r: int, ch: network.Channel, out) -> int:
        """One TAG_DATA frame from rank ``r`` straight into ``out``
        (the recv-into mirror of _recv_ctrl): out-of-band frames are
        absorbed — from the spill when they exceed ``out`` (a METRICS
        or ABORT frame may well be bigger than a small data payload),
        overwritten in place otherwise."""
        view = memoryview(network.as_byte_view(out))
        while True:
            try:
                tag, n, spill = ch.recv_into_spill(view)
            except WorldAbortedError:
                raise
            except (ConnectionError, OSError) as e:
                raise _abort_error(
                    r, f"control channel to {ch.peer} failed: {e}") \
                    from e
            if tag == TAG_PING:
                continue
            if tag == TAG_METRICS:
                self._on_metrics(r, spill if spill is not None
                                 else bytes(view[:n]))
                continue
            if tag == TAG_TRACE:
                self._on_trace_frame(r, spill if spill is not None
                                     else bytes(view[:n]))
                continue
            if tag == TAG_ABORT:
                origin, cause = heartbeat.decode_abort(
                    spill if spill is not None else bytes(view[:n]))
                raise _abort_error(origin, cause, resolved=True)
            if tag != TAG_DATA:
                raise ConnectionError(
                    f"expected tag {TAG_DATA} from rank {r}, got {tag}")
            if spill is not None:
                raise ConnectionError(
                    f"data frame of {n} bytes from rank {r} overflows "
                    f"{len(view)}-byte buffer")
            if self._metrics_on:
                self._last_seen[r] = time.monotonic()
                self._m_ctrl_rx.inc(n)
            return n

    def gather_data_into(self, payload, outs) -> Optional[List[int]]:
        if self._has_aggregates:
            # Hierarchical owners deliver pack_frames aggregates —
            # per-rank payloads interleave inside one frame, so this
            # tier takes the classic gather and one copy per rank.
            gathered = self.gather_data(payload)
            lens = [0] * self._size
            for r in range(1, self._size):
                data = gathered[r]
                mv = memoryview(network.as_byte_view(outs[r]))
                mv[:len(data)] = data
                lens[r] = len(data)
            return lens
        if self._reactor and self._fanout is not None \
                and self._fanout.batched_ok:
            return self._gather_data_into_batched(outs)
        lens = [0] * self._size
        try:
            for r, ch in self._channels.items():
                lens[r] = self._recv_data_into(r, ch, outs[r])
        except WorldAbortedError:
            raise
        except (ConnectionError, OSError) as e:
            self._raise_transport(e)
        return lens

    def _gather_data_into_batched(self, outs) -> List[int]:
        """Reactor data gather: every worker's TAG_DATA frame lands
        straight in its preallocated buffer through ONE batched
        readiness submission per wakeup (_NativeFanout.gather_into)
        instead of N sequential Python recv loops. Wire-identical to
        the sequential path — only this rank's recv scheduling
        changes — so HOROVOD_TPU_REACTOR may differ across ranks."""
        fan = self._fanout
        lens = [0] * self._size
        try:
            got, _arrivals, batches = fan.gather_into(
                TAG_DATA, {r: outs[r] for r in self._channels})
        except WorldAbortedError:
            raise
        except (ConnectionError, OSError) as e:
            self._raise_transport(e)
        for r, n in got.items():
            lens[r] = n
        hist = self._m_reactor_batch
        if hist is not None:
            for b in batches:
                hist.observe(b)
        if self._metrics_on:
            now = time.monotonic()
            for r in got:
                self._last_seen[r] = now
            self._m_ctrl_rx.inc(sum(got.values()))
        return lens

    def broadcast_data_into(self, payload, out,
                            root_rank: int = 0) -> int:
        try:
            if root_rank == 0:
                payload = _as_buffer(payload)
                assert payload is not None
                if self._metrics_on:
                    self._m_ctrl_tx.inc(
                        len(payload) * len(self._channels))
                if self._fanout is not None:
                    self._fanout.send_all(payload, TAG_DATA)
                else:
                    for ch in self._channels.values():
                        ch.send(payload, TAG_DATA)
                return len(payload)
            owner = self._owner_of[root_rank]
            n = self._recv_data_into(owner, self._channels[owner], out)
            view = memoryview(network.as_byte_view(out))[:n]
            if self._fanout is not None:
                self._fanout.send_all(view, TAG_DATA,
                                      exclude_rank=owner)
            else:
                for r, ch in self._channels.items():
                    if r != owner:
                        ch.send(view, TAG_DATA)
            return n
        except WorldAbortedError:
            raise
        except (ConnectionError, OSError) as e:
            self._raise_transport(e)

    def scatter_data_into(self, payloads, out) -> int:
        assert payloads is not None and len(payloads) == self._size
        self.scatter_data(payloads)  # send-only for the coordinator
        return len(_as_buffer(payloads[0]))

    # -- native steady cycle ---------------------------------------------
    def steady_native_ready(self) -> bool:
        if self._has_aggregates or not self._channels:
            return False
        from horovod_tpu import native as _native
        return _native.get() is not None

    def steady_spec_cycle(self, plan, bufs):
        from horovod_tpu import native as _native
        from horovod_tpu.common import steady as _steady
        lib = _native.get()
        if lib is None or self._has_aggregates or not plan.native_ok \
                or not self._channels:
            return None
        ranks = sorted(self._channels)
        fds = []
        for r in ranks:
            try:
                fd = self._channels[r].sock.fileno()
            except OSError:
                fd = -1
            if fd < 0:
                raise _abort_error(
                    r, f"connection to rank {r} lost before the "
                       f"steady cycle")
            fds.append(fd)
        hb = None
        if self._hb_timeout and self._hb_timeout > 0:
            hb = _hb_normalized(self._hb_timeout, self._hb_interval)
            if self._steady_on_idle is None:
                self._steady_on_idle = _native.ON_IDLE_FUNC(
                    self._ping_peers)
        if self._steady_scratch is None:
            from horovod_tpu.common.arena import FusionArena
            self._steady_scratch = FusionArena()

        def on_oob(idx: int, tag: int, payload: bytes) -> bool:
            if tag == TAG_METRICS:
                self._on_metrics(ranks[idx], payload)
                return True
            if tag == TAG_TRACE:
                self._on_trace_frame(ranks[idx], payload)
                return True
            return False

        kind, val = _steady.run_coord_cycle(
            lib, plan, fds, self._secret, bufs, bytes((TAG_PING,)),
            TAG_REQUESTS, TAG_RESPONSES, hb,
            self._steady_on_idle if hb is not None else None,
            self._steady_scratch, on_oob)
        if kind == _steady.DONE:
            segs, arrive = val
            on_arrivals = self._on_arrivals  # one read; see _gather_frames
            if on_arrivals is not None:
                # The native steady gather stamps per-peer arrivals in
                # C (CLOCK_MONOTONIC); 0.0 marks a frame absorbed in a
                # previous resumed slice — skip it rather than invent
                # a lag. Rank 0's own contribution is "already there".
                arrivals = {r: t for r, t in zip(ranks, arrive) if t}
                if arrivals:
                    arrivals[0] = min(arrivals.values())
                    on_arrivals(arrivals)
            if self._metrics_on:
                now = time.monotonic()
                nbytes = plan.payload_nbytes
                for r in ranks:
                    self._last_seen[r] = now
                self._m_ctrl_rx.inc(nbytes * len(ranks))
                self._m_ctrl_tx.inc(nbytes * len(ranks))
            return ("done", segs)
        if kind == _steady.DEV:
            idx, tag, payload, done, peer_views = val
            if tag == TAG_ABORT:
                origin, cause = heartbeat.decode_abort(payload)
                raise _abort_error(origin, cause, resolved=True)
            if tag != TAG_REQUESTS:
                raise ConnectionError(
                    f"expected tag {TAG_REQUESTS} from rank "
                    f"{ranks[idx]}, got {tag}")
            # Classic fallback: rank-indexed frames — absorbed steady
            # frames re-serialize from scratch, the deviant frame rides
            # as-is, everyone still owed delivers classically.
            out = [b""] * self._size
            out[0] = plan.frame_bytes(bufs)
            out[ranks[idx]] = payload
            try:
                for i, r in enumerate(ranks):
                    if done[i]:
                        out[r] = _steady.peer_frame_bytes(
                            plan, peer_views[i])
                    elif i != idx:
                        out[r] = self._recv_ctrl(r, self._channels[r],
                                                 TAG_REQUESTS)
            except WorldAbortedError:
                raise
            except (ConnectionError, OSError) as e:
                self._raise_transport(e)
            return ("fallback", out)
        rc, done = val
        if rc == _steady.ETIMEDOUT:
            waiting = [ranks[i] for i in range(len(ranks))
                       if not done[i]]
            raise _abort_error(
                waiting[0] if waiting else -1,
                f"no control frame from rank(s) {waiting} for "
                f"{self._hb_timeout:g}s — peer presumed dead "
                f"(heartbeat timeout; raise HOROVOD_HEARTBEAT_TIMEOUT "
                f"if peers legitimately stall longer)")
        self._raise_transport(ConnectionError(
            f"native steady cycle failed: errno {-rc}"))

    def worker_peer_ip(self, rank: int) -> str:
        """IP of worker ``rank`` as seen from this coordinator — the
        address other ranks use to reach that worker's data listener
        (ring rendezvous, ops/ring.py). Under the hierarchical control
        plane a shared-netns leaf shares its host's IP, so its local
        root's channel answers for it; a leaf with its own network
        identity (non-loopback connect to its root) reported its real
        IP at setup and that override wins."""
        ip = self._peer_ip_override.get(rank)
        if ip is not None:
            return ip
        return self._channels[self._owner_of[rank]].sock.getpeername()[0]

    def abort(self, origin_rank: int, cause: str) -> None:
        payload = heartbeat.encode_abort(origin_rank, cause)
        for ch in self._channels.values():
            try:
                ch.send(payload, TAG_ABORT)
            except Exception:
                pass  # that peer is already dead/unreachable

    def sever_connection(self, target_rank: Optional[int] = None) -> None:
        if target_rank is not None:
            owner = self._owner_of.get(target_rank, target_rank)
            ch = self._channels.get(owner)
            if ch is not None:
                ch.close()
            return
        for ch in self._channels.values():
            ch.close()

    def drain_abort_notice(self, grace_s: float = 0.0) -> Optional[tuple]:
        return _drain_abort(self._channels, grace_s)

    def close(self) -> None:
        for ch in self._channels.values():
            try:
                ch.close()
            except OSError:
                pass  # stage-guarded: the listener must still close
        self._server.close()


class TcpWorker(Controller):
    """Ranks 1..size-1: one persistent connection upward.

    Flat world: the upward channel goes straight to the coordinator.
    Hierarchical world (coordinator announced ``hier`` in the
    handshake): a remote host's local_rank-0 process becomes the host's
    LOCAL ROOT — it keeps the coordinator channel, accepts loopback
    connections from its host's leaf ranks, and relays every
    control/data primitive between them and the coordinator, packing
    the host's per-rank frames into one aggregate frame upward
    (pack_frames). Remote leaf ranks migrate: they drop the coordinator
    channel and point their upward channel at the local root instead —
    every op below then works unchanged for them. This is the
    control-plane rendering of the reference's LOCAL/CROSS communicator
    split (reference: horovod/common/operations.cc:729-764). The root's
    per-cycle child fan-in/fan-out rides the same native poll(2) hot
    path as the coordinator's (_NativeFanout), so the hierarchy adds a
    hop without adding a Python per-channel loop."""

    def __init__(self, rank: int, size: int, addr: str, port: int,
                 secret: bytes = b"", start_timeout: float = 30.0,
                 heartbeat_interval: float = 5.0,
                 heartbeat_timeout: float = 30.0,
                 elastic_port: Optional[int] = None,
                 world_id: int = 0):
        self.coordinator_addr = addr  # rank 0's reachable address
        self._hb_interval = heartbeat_interval
        self._hb_timeout = heartbeat_timeout
        self._ping_seq = 0
        self._last_ping = 0.0
        self._world_id = world_id
        self._up_rank = 0  # who the upward channel talks to
        self._ch = network.connect(addr, port, secret,
                                   timeout=start_timeout,
                                   retry_deadline=start_timeout)
        self._ch.peer = f"coordinator ({self._ch.peer})"
        hello_d = {"rank": rank, "hostname": _my_hostname()}
        if elastic_port is not None:
            hello_d["elastic_port"] = elastic_port
        if world_id:
            hello_d["world_id"] = world_id
        hello = json.dumps(hello_d).encode()
        self._ch.send(hello, TAG_HANDSHAKE)
        tag, payload = self._ch.recv()
        if tag != TAG_HANDSHAKE:
            raise ConnectionError("handshake failed")
        info = json.loads(payload.decode())
        coord_wid = int(info.get("world_id", 0))
        if coord_wid != world_id:
            raise ConnectionError(
                f"dialed a coordinator serving world {coord_wid:#010x} "
                f"while joining world {world_id:#010x} — two "
                f"sub-worlds are sharing a port (check the derived "
                f"sub-world coordinator ports)")
        # Tenant descriptor broadcast by the coordinator (tenancy.py):
        # the world-replicated scheduling knobs — the coordinator's
        # values win over any rank-local env, like the fusion
        # threshold.
        self.tenant_desc = info.get("tenant")
        hostnames = info["hostnames"]
        # Elastic re-rendezvous endpoint map (rank 0's host is the
        # address this worker dialed — provably reachable from here).
        self.elastic_endpoints: Optional[Dict[int, tuple]] = None
        if info.get("elastic") is not None:
            em = info["elastic"]
            self.elastic_endpoints = {0: (addr, int(em["coord_port"]))}
            for r_s, p in em["ports"].items():
                self.elastic_endpoints[int(r_s)] = \
                    (em["ips"][r_s], int(p))
        self.topology = compute_topology(rank, hostnames)
        # rank -> loopback channel of each local leaf (local roots only)
        self._children: Dict[int, network.Channel] = {}
        self._child_fanout: Optional[_NativeFanout] = None
        self._members: List[int] = [rank]  # this host's ranks, ascending
        # leaf rank -> its latest raw METRICS frame: folded with this
        # root's own snapshot into ONE frame upward (send_metrics) so
        # coordinator metrics fan-in scales with hosts, like CACHED_AGG.
        self._child_metrics: Dict[int, bytes] = {}
        # Accumulated leaf TRACE frames (NOT latest-wins: spans are
        # one-shot deltas — every frame must forward exactly once).
        # Concatenated into this root's own frame by send_trace;
        # bounded so a wedged upward channel cannot grow it forever.
        self._child_trace: List[bytes] = []
        # liveness timestamps for peer_heartbeat_ages (metrics only)
        self._up_seen = time.monotonic()
        self._child_seen: Dict[int, float] = {}
        # Reusable landing buffer for the chunked cut-through relay's
        # bytes-returning legs (lazily sized; frames past its capacity
        # spill to a native malloc for that call only).
        self._relay_buf: Optional[bytearray] = None
        if (info.get("hier") and self.topology.cross_rank != 0
                and self.topology.local_size > 1):
            _, host_members = host_groups(hostnames)
            members = host_members[self.topology.cross_rank]
            if self.topology.local_rank == 0:
                self._become_local_root(members, secret, start_timeout)
            else:
                self._become_leaf(rank, secret, start_timeout)
        hb = None
        if self._hb_timeout and self._hb_timeout > 0:
            hb = _hb_normalized(self._hb_timeout, self._hb_interval) \
                + (self._ping_children,)
            self._ch.arm(self._hb_timeout, self._hb_interval)
            for r, ch in self._children.items():
                ch.arm(self._hb_timeout, self._hb_interval,
                       on_idle=self._ping_children)
        if self._children:
            self._child_fanout = _NativeFanout.create(
                self._children, secret, hb=hb,
                on_metrics=self._on_child_metrics,
                on_trace=self._on_child_trace)

    def _become_local_root(self, members: List[int], secret: bytes,
                           start_timeout: float) -> None:
        """Open a same-host listener, report its port upward, accept
        this host's leaf ranks."""
        srv = network.listen(0, host=_local_root_addr())
        port = srv.getsockname()[1]
        self._ch.send(json.dumps({"port": port}).encode(), TAG_HANDSHAKE)
        expected = set(members[1:])

        def _validate(hello):
            r = int(hello["rank"])
            if r not in expected:
                raise ConnectionError(f"unexpected rank {r}")
            return r

        accepts = _accept_handshakes(
            srv, secret, time.monotonic() + start_timeout,
            lambda: (f"local root {self.rank}: leaves "
                     f"{sorted(expected)} did not connect within start "
                     f"timeout"),
            _validate)
        while expected:
            r, _, ch = next(accepts)
            ch.send(b"{}", TAG_HANDSHAKE)  # accept ack
            ch.peer = f"rank {r} ({ch.peer})"
            # hvdlint: owned-by=main -- rendezvous runs before the world's cycle threads start (Thread.start happens-before publishes it)
            self._children[r] = ch
            expected.discard(r)
        srv.close()
        self._members = members
        # Report the IPs the leaves connected from so the coordinator
        # can answer worker_peer_ip correctly when leaves have their
        # own network identity (non-loopback deployments).
        leaf_ips = {r: ch.sock.getpeername()[0]
                    for r, ch in self._children.items()}
        self._ch.send(json.dumps({"leaf_ips": leaf_ips}).encode(),
                      TAG_HANDSHAKE)

    def _become_leaf(self, rank: int, secret: bytes,
                     start_timeout: float) -> None:
        """Receive the root-port map, then swap the upward channel from
        the coordinator to this host's local root."""
        tag, data = self._ch.recv()
        if tag != TAG_HANDSHAKE:
            raise ConnectionError(
                f"expected root-port map, got tag {tag}")
        ports = json.loads(data.decode())["roots"]
        port = int(ports[str(self.topology.cross_rank)])
        self._ch.close()
        # hvdlint: owned-by=main -- rendezvous channel swap happens before the world's cycle threads start (Thread.start happens-before publishes it)
        self._ch = network.connect(_local_root_addr(), port, secret,
                                   timeout=start_timeout,
                                   retry_deadline=start_timeout)
        root = self.topology.local_roots[self.topology.cross_rank]
        self._up_rank = root
        self._ch.peer = f"local root rank {root} ({self._ch.peer})"
        self._ch.send(json.dumps({"rank": rank}).encode(), TAG_HANDSHAKE)
        tag, _ = self._ch.recv()
        if tag != TAG_HANDSHAKE:
            raise ConnectionError("local root handshake failed")

    # -- per-cycle primitives (relay through _children when present) -----
    def _ping_children(self) -> None:
        """Fired per idle slice of the child gather (a straggler leaf
        must not look dead to its waiting siblings)."""
        if self._children:
            _maybe_ping(self, self._children, self.rank)

    def _on_child_metrics(self, r: int, payload: bytes) -> None:
        """A leaf's METRICS frame: keep only the LATEST per leaf —
        snapshots are totals, so folding the most recent frame from
        each member is exact regardless of drop/reorder."""
        self._child_metrics[r] = payload
        if self._metrics_on:
            self._child_seen[r] = time.monotonic()

    def _on_child_trace(self, r: int, payload: bytes) -> None:
        """A leaf's TRACE frame: ACCUMULATE (spans are deltas, not
        totals) until send_trace folds the batch upward. Past the cap
        the oldest frame is dropped — lossy beats unbounded."""
        if len(self._child_trace) >= 64:
            del self._child_trace[0]
        self._child_trace.append(payload)
        if self._metrics_on:
            self._child_seen[r] = time.monotonic()

    def send_metrics(self, payload: bytes) -> None:
        try:
            if self._child_metrics:
                # drop_incompatible: ONE leaf on skewed code must not
                # silence the root and every healthy sibling forever —
                # its frame is skipped, the rest of the host reports.
                payload = wire.combine_metrics_frames(
                    [payload] + [self._child_metrics[r]
                                 for r in sorted(self._child_metrics)],
                    drop_incompatible=True)
            self._ch.send(payload, TAG_METRICS)
            if self._metrics_on:
                self._m_ctrl_tx.inc(len(payload))
        except Exception:
            pass  # best-effort: the cycle path owns channel errors

    def send_trace(self, payload: bytes) -> None:
        try:
            if self._child_trace:
                batch, self._child_trace = self._child_trace, []
                payload = wire.combine_trace_frames([payload] + batch)
            self._ch.send(payload, TAG_TRACE)
            if self._metrics_on:
                self._m_ctrl_tx.inc(len(payload))
        except Exception:
            pass  # best-effort, like send_metrics

    def peer_heartbeat_ages(self) -> Dict[int, float]:
        if not self._metrics_on:
            # _up_seen/_child_seen are only maintained with metrics
            # attached; reporting the stale __init__ stamp would feed
            # the stall report an ever-growing bogus age for a
            # perfectly healthy upward peer.
            return {}
        now = time.monotonic()
        ages = {self._up_rank: now - self._up_seen}
        for r, t in list(self._child_seen.items()):
            ages[r] = now - t
        return ages

    def _relay_children_safe(self, data, tag: int) -> None:
        """Best-effort PING/ABORT relay downward — never raises (runs
        on liveness/failure paths)."""
        for ch in self._children.values():
            try:
                ch.send(data, tag)
            except Exception:
                pass

    def _recv_up(self, expect_tag: int) -> bytes:
        """One frame from the upward channel. PINGs prove the world is
        alive (forwarded down so leaf deadlines reset too); ABORT
        relays down then raises; silence past the heartbeat deadline
        or a dead socket names the upward peer as the origin."""
        while True:
            try:
                tag, data = self._ch.recv()
            except WorldAbortedError:
                raise
            except (ConnectionError, OSError) as e:
                raise _abort_error(
                    self._up_rank,
                    f"control channel to {self._ch.peer} failed: {e}") \
                    from e
            if self._metrics_on:
                self._up_seen = time.monotonic()
            if tag == TAG_PING:
                if self._trace_on:
                    self._note_ping(data)
                self._relay_children_safe(data, TAG_PING)
                continue
            if tag in (TAG_METRICS, TAG_TRACE):
                continue  # these only flow upward; tolerate strays
            if tag == TAG_ABORT:
                origin, cause = heartbeat.decode_abort(data)
                self._relay_children_safe(data, TAG_ABORT)
                raise _abort_error(origin, cause, resolved=True)
            if tag != expect_tag:
                raise ConnectionError(
                    f"expected tag {expect_tag} from {self._ch.peer}, "
                    f"got {tag}")
            if self._metrics_on:
                self._m_ctrl_rx.inc(len(data))
            return data

    def _note_ping(self, data: bytes) -> None:
        """Clock-sync t2: a coordinator PING's receipt stamp, the
        worker half of the NTP exchange (common/trace.py). Garbled
        pings are liveness regardless — never an error here. Tenant
        workers skip the note entirely (the send-side guard in
        _maybe_ping has this as its receive-side mirror): a TENANT
        coordinator's ping sequence is a different stream, and its
        (sender==0, seq) stamps would overwrite the process-global
        ClockSync's pending echo and poison the DEFAULT world's
        offset table."""
        if self._world_id:
            return
        try:
            sender, seq = heartbeat.decode_ping(data)
        except ValueError:
            return
        from horovod_tpu.common import trace as htrace
        htrace.clock().ping_received(sender, seq, time.monotonic())

    def _recv_child(self, r: int, tag: int) -> bytes:
        while True:
            try:
                t, data = self._children[r].recv()
            except WorldAbortedError:
                raise
            except (ConnectionError, OSError) as e:
                raise _abort_error(
                    r, f"control channel to local rank {r} failed: {e}") \
                    from e
            if t == TAG_METRICS:
                self._on_child_metrics(r, data)
                continue
            if t == TAG_TRACE:
                self._on_child_trace(r, data)
                continue
            if t == TAG_ABORT:
                origin, cause = heartbeat.decode_abort(data)
                raise _abort_error(origin, cause, resolved=True)
            if t != tag:
                raise ConnectionError(
                    f"expected tag {tag} from local rank {r}, got {t}")
            if self._metrics_on:
                self._child_seen[r] = time.monotonic()
            return data

    def _raise_child_transport(self, e: Exception, what: str):
        """Turn an anonymous transport error on the leaf tier into a
        named blame: a probed-dead leaf if there is one, else this
        rank (mirror of TcpCoordinator._raise_transport)."""
        dead = _dead_peers(self._children)
        origin = dead[0] if dead else self.rank
        raise _abort_error(origin, f"{what} failed: {e}") from e

    def _send_children(self, data, tag: int,
                       exclude_rank: Optional[int] = None) -> None:
        try:
            if self._child_fanout is not None:
                self._child_fanout.send_all(data, tag,
                                            exclude_rank=exclude_rank)
                return
            for r, ch in self._children.items():
                if r != exclude_rank:
                    ch.send(data, tag)
        except (ConnectionError, OSError) as e:
            self._raise_child_transport(e, "relay to local leaves")

    def _send_up(self, payload, tag: int) -> None:
        if self._metrics_on:
            self._m_ctrl_tx.inc(len(payload))
        try:
            self._ch.send(payload, tag)
        except (ConnectionError, OSError) as e:
            raise _abort_error(
                self._up_rank,
                f"control channel to {self._ch.peer} failed: {e}") \
                from e

    def _gather_up(self, payload, tag: int) -> None:
        if self._children:
            try:
                if self._child_fanout is not None:
                    frames = self._child_fanout.gather(tag)
                else:
                    frames = {r: self._recv_child(r, tag)
                              for r in self._children}
            except WorldAbortedError:
                raise
            except (ConnectionError, OSError) as e:
                self._raise_child_transport(e, "gather from local leaves")
            frames[self.rank] = payload
            ordered = [frames[r] for r in self._members]
            payload = None
            if tag == TAG_REQUESTS:
                # Steady-state fast path: when the whole host sent
                # cache bitmask frames, AND/OR-fold them here and
                # forward ONE mask for the host — the coordinator's
                # per-cycle bytes then scale with n_hosts, not ranks.
                # Unfoldable mixes get an explicit PACKED envelope so
                # the coordinator can tell a per-rank pack from a
                # folded frame without sniffing ambiguous bytes (a
                # raw pack_frames blob starts with its u32 count —
                # 2 for a 2-rank host, which IS the CACHED_AGG kind).
                payload = wire.combine_cycle_requests(ordered)
                if payload is None:
                    payload = wire.PACKED_PREFIX + pack_frames(ordered)
            if payload is None:
                payload = pack_frames(ordered)
        self._send_up(payload, tag)

    def gather_requests(self, payload: bytes) -> Optional[List[bytes]]:
        self._gather_up(payload, TAG_REQUESTS)
        return None

    def broadcast_responses(self, payload: Optional[bytes]) -> bytes:
        if self._relay_native_ok():
            return self._relay_up_to_children(TAG_RESPONSES)[1]
        data = self._recv_up(TAG_RESPONSES)
        self._send_children(data, TAG_RESPONSES)
        return data

    def gather_data(self, payload: bytes) -> Optional[List[bytes]]:
        self._gather_up(_as_buffer(payload), TAG_DATA)
        return None

    def broadcast_data(self, payload: Optional[bytes],
                       root_rank: int = 0) -> bytes:
        payload = _as_buffer(payload)
        if payload is not None and self.rank == root_rank:
            # Root sends up; the coordinator fans out to the other
            # channels only — our own copy is already authoritative,
            # and our local leaves get it straight from us.
            self._send_up(payload, TAG_DATA)
            self._send_children(payload, TAG_DATA)
            return payload
        if root_rank in self._children:
            # The root is one of our leaves: relay its payload upward
            # and to its local siblings; the coordinator serves the
            # rest of the world and skips this whole host.
            data = self._recv_child(root_rank, TAG_DATA)
            self._send_up(data, TAG_DATA)
            self._send_children(data, TAG_DATA, exclude_rank=root_rank)
            return data
        if self._relay_native_ok():
            return self._relay_up_to_children(TAG_DATA)[1]
        data = self._recv_up(TAG_DATA)
        self._send_children(data, TAG_DATA)
        return data

    def scatter_data(self, payloads: Optional[List[bytes]]) -> bytes:
        data = self._recv_up(TAG_DATA)
        if self._children:
            frames = unpack_frames(data)
            mine: Optional[bytes] = None
            per_child: Dict[int, bytes] = {}
            for r, f in zip(self._members, frames):
                if r == self.rank:
                    mine = f
                else:
                    per_child[r] = f
            try:
                if self._child_fanout is not None:
                    self._child_fanout.scatter(per_child, TAG_DATA)
                else:
                    for r, f in per_child.items():
                        self._children[r].send(f, TAG_DATA)
            except (ConnectionError, OSError) as e:
                self._raise_child_transport(e, "scatter to local leaves")
            assert mine is not None
            return mine
        return data

    def _recv_up_into(self, out, expect_tag: int) -> int:
        """Recv-into mirror of _recv_up: the payload lands straight in
        ``out``; PINGs relay downward and ABORT raises, exactly like
        the bytes path. Out-of-band frames bigger than ``out`` arrive
        via the spill (a PING can exceed a 0-byte scatter slice), so
        liveness and abort semantics hold at ANY destination size."""
        view = memoryview(network.as_byte_view(out))
        while True:
            try:
                tag, n, spill = self._ch.recv_into_spill(view)
            except WorldAbortedError:
                raise
            except (ConnectionError, OSError) as e:
                raise _abort_error(
                    self._up_rank,
                    f"control channel to {self._ch.peer} failed: {e}") \
                    from e
            if self._metrics_on:
                self._up_seen = time.monotonic()
            if tag == TAG_PING:
                data = spill if spill is not None else bytes(view[:n])
                if self._trace_on:
                    self._note_ping(data)
                self._relay_children_safe(data, TAG_PING)
                continue
            if tag in (TAG_METRICS, TAG_TRACE):
                continue  # these only flow upward; tolerate strays
            if tag == TAG_ABORT:
                data = spill if spill is not None else bytes(view[:n])
                origin, cause = heartbeat.decode_abort(data)
                self._relay_children_safe(data, TAG_ABORT)
                raise _abort_error(origin, cause, resolved=True)
            if tag != expect_tag:
                raise ConnectionError(
                    f"expected tag {expect_tag} from {self._ch.peer}, "
                    f"got {tag}")
            if spill is not None:
                raise ConnectionError(
                    f"frame of {n} bytes from {self._ch.peer} "
                    f"overflows {len(view)}-byte buffer")
            if self._metrics_on:
                self._m_ctrl_rx.inc(n)
            return n

    # -- chunked cut-through relay (docs/performance.md Layer 6) ---------
    def _relay_native_ok(self) -> bool:
        """The cast-while-receiving relay leg is available: reactor on
        for this rank, leaves to serve, and a native core exporting
        hvd_relay_frame (a stale pre-reactor .so keeps the
        store-and-forward path — the wire is identical either way)."""
        if not (self._reactor and self._children):
            return False
        from horovod_tpu import native as _native
        lib = _native.get()
        return lib is not None and hasattr(lib, "hvd_relay_frame")

    def _relay_up_to_children(self, expect_tag: int, out=None):
        """One upward frame relayed to every leaf cast-while-receiving
        (hvd_relay_frame): header + digest go downstream before the
        first payload byte, then each _RELAY_CHUNK_BYTES chunk forwards
        as it arrives — replacing the recv-whole-frame-then-send
        store-and-forward of _recv_up + _send_children with a
        cut-through pipeline, wire byte-identical. METRICS/TRACE strays
        are dropped in C (same tolerance as _recv_up); PING and ABORT
        bounce back here so liveness relays downward and abort decode
        keep their exact sequential semantics. Returns ``(nbytes,
        payload)`` — payload is bytes when ``out`` is None, else None
        with the frame landed in ``out``."""
        import ctypes as ct
        from horovod_tpu import native as _native
        lib = _native.get()
        if out is not None:
            mv = memoryview(network.as_byte_view(out))
        else:
            if self._relay_buf is None:
                self._relay_buf = bytearray(1 << 20)
            mv = memoryview(self._relay_buf)
        win = (ct.c_uint8 * len(mv)).from_buffer(mv) if len(mv) \
            else (ct.c_uint8 * 1)()
        kids = sorted(self._children)
        child_fds = (ct.c_int * len(kids))(
            *[self._children[r].sock.fileno() for r in kids])
        try:
            up_fd = self._ch.sock.fileno()
        except OSError:
            up_fd = -1
        if up_fd < 0:
            raise _abort_error(
                self._up_rank,
                f"control channel to {self._ch.peer} closed before "
                f"the relay")
        secret = self._ch.secret or b""
        sbuf = (ct.c_uint8 * max(1, len(secret))).from_buffer_copy(
            secret or b"\x00")
        skip = (ct.c_uint8 * 2)(TAG_METRICS, TAG_TRACE)
        if self._hb_timeout and self._hb_timeout > 0:
            t_s, i_s = _hb_normalized(self._hb_timeout,
                                      self._hb_interval)
            timeout_ms = max(1, int(t_s * 1000))
            interval_ms = max(1, int(i_s * 1000))
        else:
            timeout_ms = interval_ms = -1
        out_len = ct.c_int64(0)
        out_tag = ct.c_uint8(0)
        spill = ct.POINTER(ct.c_uint8)()
        while True:
            rc = lib.hvd_relay_frame(
                up_fd, child_fds, len(kids), expect_tag,
                ct.addressof(win), len(mv), sbuf, len(secret),
                skip, 2, _RELAY_CHUNK_BYTES, timeout_ms, interval_ms,
                ct.byref(out_len), ct.byref(out_tag), ct.byref(spill))
            if rc == 2:
                # Deviation: an authenticated non-stray frame that is
                # NOT the expected one — it was absorbed, not relayed.
                tag = out_tag.value
                if spill:
                    payload = ct.string_at(spill, out_len.value)
                    lib.hvd_free(spill)
                    spill = ct.POINTER(ct.c_uint8)()
                else:
                    payload = b""
                if self._metrics_on:
                    self._up_seen = time.monotonic()
                if tag == TAG_PING:
                    if self._trace_on:
                        self._note_ping(payload)
                    self._relay_children_safe(payload, TAG_PING)
                    continue
                if tag == TAG_ABORT:
                    origin, cause = heartbeat.decode_abort(payload)
                    self._relay_children_safe(payload, TAG_ABORT)
                    raise _abort_error(origin, cause, resolved=True)
                raise ConnectionError(
                    f"expected tag {expect_tag} from {self._ch.peer}, "
                    f"got {tag}")
            if rc == 1:
                # Expected frame, relayed, but bigger than the landing
                # buffer: the payload rode through a native spill.
                n = out_len.value
                payload = ct.string_at(spill, n) if spill else b""
                if spill:
                    lib.hvd_free(spill)
                    spill = ct.POINTER(ct.c_uint8)()
                if out is not None:
                    raise ConnectionError(
                        f"frame of {n} bytes from {self._ch.peer} "
                        f"overflows {len(mv)}-byte buffer")
                if self._metrics_on:
                    self._up_seen = time.monotonic()
                    self._m_ctrl_rx.inc(n)
                return n, payload
            if rc == 0:
                n = out_len.value
                if self._metrics_on:
                    self._up_seen = time.monotonic()
                    self._m_ctrl_rx.inc(n)
                return n, (bytes(mv[:n]) if out is None else None)
            if rc == -errno.ETIMEDOUT:
                raise _abort_error(
                    self._up_rank,
                    f"no data from {self._ch.peer} for "
                    f"{self._hb_timeout:g}s — peer presumed dead "
                    f"(heartbeat timeout; raise "
                    f"HOROVOD_HEARTBEAT_TIMEOUT if peers legitimately "
                    f"stall longer)")
            # A child write failure surfaces with the same negative rc
            # as an upward read failure — probe the leaves to blame
            # the right tier (mirror of _raise_child_transport).
            dead = _dead_peers(self._children)
            if dead:
                raise _abort_error(
                    dead[0],
                    f"relay to local leaves failed: errno {-rc}")
            raise _abort_error(
                self._up_rank,
                f"control channel to {self._ch.peer} failed during "
                f"the chunked relay: errno {-rc}")

    def gather_data_into(self, payload, outs) -> Optional[List[int]]:
        self._gather_up(_as_buffer(payload), TAG_DATA)
        return None

    def broadcast_data_into(self, payload, out,
                            root_rank: int = 0) -> int:
        if payload is not None and self.rank == root_rank:
            payload = _as_buffer(payload)
            self._send_up(payload, TAG_DATA)
            self._send_children(payload, TAG_DATA)
            return len(payload)
        if root_rank in self._children:
            data = self._recv_child(root_rank, TAG_DATA)
            self._send_up(data, TAG_DATA)
            self._send_children(data, TAG_DATA, exclude_rank=root_rank)
            mv = memoryview(network.as_byte_view(out))
            mv[:len(data)] = data
            return len(data)
        if self._relay_native_ok():
            return self._relay_up_to_children(TAG_DATA, out=out)[0]
        n = self._recv_up_into(out, TAG_DATA)
        if self._children:
            self._send_children(
                memoryview(network.as_byte_view(out))[:n], TAG_DATA)
        return n

    def scatter_data_into(self, payloads, out) -> int:
        if self._children:
            # A local root must unpack the aggregate to relay each
            # leaf's slice — the classic path with one copy out.
            data = self.scatter_data(payloads)
            mv = memoryview(network.as_byte_view(out))
            mv[:len(data)] = data
            return len(data)
        return self._recv_up_into(out, TAG_DATA)

    # -- native steady cycle ---------------------------------------------
    def steady_native_ready(self) -> bool:
        if self._children:
            return False
        from horovod_tpu import native as _native
        return _native.get() is not None

    def steady_spec_cycle(self, plan, bufs):
        from horovod_tpu import native as _native
        from horovod_tpu.common import steady as _steady
        lib = _native.get()
        if lib is None or self._children or not plan.native_ok:
            return None
        try:
            fd = self._ch.sock.fileno()
        except OSError:
            fd = -1
        if fd < 0:
            raise _abort_error(
                self._up_rank,
                f"control channel to {self._ch.peer} closed before "
                f"the steady cycle")
        kind, val = _steady.run_worker_cycle(
            lib, plan, fd, self._ch.secret,
            bytes((TAG_PING, TAG_METRICS, TAG_TRACE)), TAG_REQUESTS,
            TAG_RESPONSES, self._ch._hb)
        if self._metrics_on:
            self._up_seen = time.monotonic()
        if kind == _steady.DONE:
            if self._metrics_on:
                self._m_ctrl_tx.inc(plan.payload_nbytes)
                self._m_ctrl_rx.inc(plan.payload_nbytes)
            return ("done", val)
        if kind == _steady.FRAME:
            tag, payload = val
            if tag == TAG_ABORT:
                origin, cause = heartbeat.decode_abort(payload)
                raise _abort_error(origin, cause, resolved=True)
            if tag != TAG_RESPONSES:
                raise ConnectionError(
                    f"expected tag {TAG_RESPONSES} from "
                    f"{self._ch.peer}, got {tag}")
            if self._metrics_on:
                self._m_ctrl_rx.inc(len(payload))
            return ("frame", payload)
        rc = val
        if rc == _steady.ETIMEDOUT:
            raise _abort_error(
                self._up_rank,
                f"no data from {self._ch.peer} for "
                f"{self._hb_timeout:g}s — peer presumed dead "
                f"(heartbeat timeout; raise HOROVOD_HEARTBEAT_TIMEOUT "
                f"if peers legitimately stall longer)")
        raise _abort_error(
            self._up_rank,
            f"control channel to {self._ch.peer} failed during the "
            f"steady cycle: errno {-rc}")

    def abort(self, origin_rank: int, cause: str) -> None:
        payload = heartbeat.encode_abort(origin_rank, cause)
        try:
            self._ch.send(payload, TAG_ABORT)  # escalate up
        except Exception:
            pass
        self._relay_children_safe(payload, TAG_ABORT)

    def sever_connection(self, target_rank: Optional[int] = None) -> None:
        if target_rank is not None and target_rank in self._children:
            self._children[target_rank].close()
            return
        self._ch.close()

    def drain_abort_notice(self, grace_s: float = 0.0) -> Optional[tuple]:
        return _drain_abort({self._up_rank: self._ch, **self._children},
                            grace_s)

    def close(self) -> None:
        for ch in self._children.values():
            try:
                ch.close()
            except OSError:
                pass  # stage-guarded: the upward channel must still close
        self._ch.close()
