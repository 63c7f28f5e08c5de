"""Native steady-cycle plans: the zero-copy data plane for the fused
speculative cycle.

PR 3 collapsed a steady training step into ONE world round-trip, but
every byte still flowed through Python: pack into a fresh buffer,
serialize into a bytes object, recv into a bytearray, copy again for
writability. This module precomputes everything that is CONSTANT
across steady steps — the CACHED_SPEC frame's prefix and per-segment
headers (from wire.spec_frame_parts, so native and pure-Python ranks
share one byte layout), the fusion-arena segment views the packed
tensors land in, and the ctypes pointer bundles the native core
consumes — so a steady step becomes: one native pack into the arena,
one ``hvd_steady_worker``/``hvd_steady_coord`` call (GIL released)
that sends, reduces and receives straight between sockets and numpy
memory, and one unpack into fresh per-entry outputs. No intermediate
bytes object is materialized anywhere on the path
(``hvd_data_copies_total`` counts the fallback copies that remain).

Role split: a plan is world-replicated LAYOUT (derived from the
granted mask — identical on every rank); per-step tensor data flows
through :meth:`SteadyPlan.pack`. Receive destinations are always
fresh per-step arrays — never arena memory — so user-visible outputs
can never be clobbered by a later step (see common/arena.py).
"""

from __future__ import annotations

import ctypes
import errno
from typing import Dict, List, Tuple

import numpy as np

from horovod_tpu import native as _native
from horovod_tpu.common import wire
from horovod_tpu.common.arena import FusionArena, concat_into

_u8p = ctypes.POINTER(ctypes.c_uint8)

# Outcome kinds shared with the controllers.
DONE = "done"       # cycle completed natively; payload = result segments
FRAME = "frame"     # worker deviation: (tag, payload bytes)
DEV = "dev"         # coordinator deviation: (peer_idx, tag, payload)
ERR = "err"         # transport failure: negative errno


class SteadyPlan:
    """Precomputed layout of one steady fused cycle (one grant mask at
    one cache epoch under one fusion threshold)."""

    __slots__ = ("epoch", "nslots", "mask", "seg_dtypes",
                 "seg_np_dtypes", "seg_nbytes", "seg_counts",
                 "seg_codes", "seg_src_dtypes", "prefix", "seg_hdrs",
                 "payload_nbytes", "arena", "send_views",
                 "stage_views", "native_ok", "cache", "chunk_bytes",
                 "chunked")

    def __init__(self, epoch: int, nslots: int, mask: int,
                 segments, arena: FusionArena, chunk_bytes: int = 0,
                 world_id: int = 0):
        """``segments``: [(DataType, np_dtype, nbytes, src_np_dtype),
        ...] in replay-plan order, where ``np_dtype``/``nbytes``
        describe the ON-WIRE representation and ``src_np_dtype`` names
        the tensors' real dtype when a negotiated wire dtype
        compresses this segment (None = uncompressed; a legacy
        3-tuple means the same).

        ``chunk_bytes`` > 0 arms chunked pipelined transfer on the
        worker half (HOROVOD_OVERLAP_CHUNK_BYTES): pack leaves
        compressed segments in their full-precision staging views and
        ``hvd_steady_worker_chunked`` casts them chunk-by-chunk
        interleaved with the send — compression of chunk i+1 overlaps
        the kernel-buffered transmission of chunk i. Wire bytes are
        identical either way."""
        self.epoch = epoch
        self.nslots = nslots
        self.mask = mask
        segments = [tuple(s) + (None,) if len(s) == 3 else tuple(s)
                    for s in segments]
        self.seg_dtypes = [s[0] for s in segments]
        self.seg_np_dtypes = [np.dtype(s[1]) for s in segments]
        self.seg_nbytes = [s[2] for s in segments]
        self.seg_src_dtypes = [None if s[3] is None else np.dtype(s[3])
                               for s in segments]
        self.seg_counts = [n // npdt.itemsize
                           for npdt, n in zip(self.seg_np_dtypes,
                                              self.seg_nbytes)]
        codes = [_native._DTYPE_CODES.get(str(npdt))
                 for npdt in self.seg_np_dtypes]
        self.seg_codes = codes
        # The native coordinator must be able to reduce every segment
        # in C; one exotic dtype degrades the whole cycle to Python.
        self.native_ok = bool(segments) and all(c is not None
                                                for c in codes)
        # Tenant worlds lead the constant prefix with the world-id
        # envelope (wire.stamp_world) so the native byte-compare and
        # the classically-serialized frame stay byte-identical.
        self.prefix, self.seg_hdrs = wire.spec_frame_parts(
            epoch, nslots, mask,
            [(dt, n) for dt, n in zip(self.seg_dtypes,
                                      self.seg_nbytes)],
            world_id=world_id)
        self.payload_nbytes = (len(self.prefix)
                               + sum(len(h) for h in self.seg_hdrs)
                               + sum(self.seg_nbytes))
        self.arena = arena
        # Send-side segment views: stable arena memory, so the iovec
        # pointers below survive across steps. Compressed segments
        # additionally get a full-precision STAGING view right after
        # the wire region — pack concatenates + prescales there, then
        # casts once into the wire view (send bytes only ever live in
        # the arena; the staging bytes never reach the wire).
        off = 0
        views = []
        wire_total = sum(self.seg_nbytes)
        stage_total = sum(
            cnt * src.itemsize
            for cnt, src in zip(self.seg_counts, self.seg_src_dtypes)
            if src is not None)
        arena.ensure(wire_total + stage_total)
        for npdt, n, count in zip(self.seg_np_dtypes, self.seg_nbytes,
                                  self.seg_counts):
            views.append(arena.typed(off, npdt, count))
            off += n
        self.send_views = views
        stages = []
        soff = wire_total
        for count, src in zip(self.seg_counts, self.seg_src_dtypes):
            if src is None:
                stages.append(None)
            else:
                stages.append(arena.typed(soff, src, count))
                soff += count * src.itemsize
        self.stage_views = stages
        # Chunked pipelined transfer engages only when a segment
        # actually compresses (src dtype present), the knob is armed,
        # and the native library exports the chunked entry point —
        # every other combination keeps the classic one-shot send.
        self.chunk_bytes = int(chunk_bytes)
        self.chunked = False
        if self.chunk_bytes > 0 and any(
                s is not None for s in self.seg_src_dtypes):
            lib = _native.get()

            def _castable(src, wire_code):
                # hvd_cast only speaks f32 <-> bf16/f16 (codes 0 <->
                # 6/5); any other pair (e.g. float64 sources) must
                # keep the Python cast + classic one-shot send, or
                # the chunk loop would -EINVAL mid-frame and abort a
                # healthy world.
                if src is None:
                    return True
                return (_native._DTYPE_CODES.get(str(src)) == 0
                        and wire_code in (5, 6))

            self.chunked = (lib is not None
                            and hasattr(lib, "hvd_steady_worker_chunked")
                            and all(
                                _castable(s, c) for s, c in
                                zip(self.seg_src_dtypes,
                                    self.seg_codes)))
        # Role-specific ctypes bundles attached by the controllers;
        # dies with the plan (plans are epoch-memoized in the runtime).
        self.cache: Dict = {}

    @property
    def nseg(self) -> int:
        return len(self.seg_nbytes)

    # -- per-step packing ------------------------------------------------
    def pack(self, seg_arrays: List[List[np.ndarray]],
             prescales: List[float],
             use_arena: bool = True) -> List[np.ndarray]:
        """Pack each segment's entry tensors into contiguous send
        buffers: the persistent arena views (workers — stable iovec
        pointers, zero allocations) or fresh accumulators
        (coordinator — its outputs alias the reduced buffers, which
        must therefore never be arena memory)."""
        from horovod_tpu.common import wire_dtype as _wd
        bufs = []
        for j, arrays in enumerate(seg_arrays):
            npdt = self.seg_np_dtypes[j]
            src_dt = self.seg_src_dtypes[j]
            flats = [a.reshape(-1) if a.flags["C_CONTIGUOUS"]
                     else np.ascontiguousarray(a).reshape(-1)
                     for a in arrays]
            if src_dt is None:
                dst = self.send_views[j] if use_arena \
                    else np.empty(self.seg_counts[j], npdt)
                concat_into(flats, dst)
                f = prescales[j]
                if f != 1.0:
                    np.multiply(dst, np.asarray(f, npdt), out=dst)
                bufs.append(dst)
                continue
            # Compressed segment: concat + prescale in the tensors'
            # real dtype (staging), one cast into the wire view — the
            # native hvd_cast kernel when it speaks the pair. With the
            # chunked worker armed the cast is DEFERRED: the native
            # send loop casts chunk-by-chunk interleaved with the
            # wire (frame_bytes materializes it for fallback paths).
            stage = self.stage_views[j] if use_arena \
                else np.empty(self.seg_counts[j], src_dt)
            concat_into(flats, stage)
            f = prescales[j]
            if f != 1.0:
                np.multiply(stage, np.asarray(f, src_dt), out=stage)
            dst = self.send_views[j] if use_arena \
                else np.empty(self.seg_counts[j], npdt)
            if not (self.chunked and use_arena):
                _wd.cast_into(stage, dst)
            bufs.append(dst)
        return bufs

    def materialize_wire(self) -> None:
        """Deferred-cast fallback: fill the wire views from staging —
        exactly the bytes the chunked native send would have produced
        (one cast pass; chunking never changes wire bytes)."""
        from horovod_tpu.common import wire_dtype as _wd
        for j, src in enumerate(self.seg_src_dtypes):
            if src is not None:
                _wd.cast_into(self.stage_views[j], self.send_views[j])

    def frame_bytes(self, bufs: List[np.ndarray]) -> bytes:
        """Serialize a full CACHED_SPEC frame from packed buffers —
        byte-identical to wire.serialize_cycle_request. Fallback paths
        only (the native path never materializes the frame)."""
        if self.chunked and any(b is v for b, v in
                                zip(bufs, self.send_views)):
            self.materialize_wire()
        parts = [self.prefix]
        for h, b in zip(self.seg_hdrs, bufs):
            parts.append(h)
            parts.append(memoryview(b.view(np.uint8)))
        return b"".join(parts)

    def result_segments(self, raw: np.ndarray):
        """[(DataType, typed view)] over a contiguous result buffer
        holding the concatenated segment data."""
        out = []
        off = 0
        for dt, npdt, n, count in zip(self.seg_dtypes,
                                      self.seg_np_dtypes,
                                      self.seg_nbytes,
                                      self.seg_counts):
            out.append((dt, raw[off:off + n].view(npdt)))
            off += n
        return out


def _mkbuf(b: bytes):
    return (ctypes.c_uint8 * max(1, len(b))).from_buffer_copy(
        b or b"\x00")


def _c_common(plan: SteadyPlan) -> Dict:
    """ctypes pieces both roles share, cached on the plan."""
    c = plan.cache.get("common")
    if c is None:
        hdr_bufs = [_mkbuf(h) for h in plan.seg_hdrs]
        c = {
            "prefix": _mkbuf(plan.prefix),
            "hdr_bufs": hdr_bufs,  # keep alive behind the pointers
            "hdr_ptrs": (_u8p * plan.nseg)(
                *[ctypes.cast(b, _u8p) for b in hdr_bufs]),
            "hdr_lens": (ctypes.c_int64 * plan.nseg)(
                *[len(h) for h in plan.seg_hdrs]),
            "seg_lens": (ctypes.c_int64 * plan.nseg)(*plan.seg_nbytes),
            "seg_codes": (ctypes.c_int * plan.nseg)(*plan.seg_codes),
        }
        plan.cache["common"] = c
    return c


def _hb_ms(hb) -> Tuple[int, int]:
    """Channel.arm's (timeout_s, interval_s, on_idle) -> native
    (timeout_ms, interval_ms); (-1, -1) blocks forever."""
    if hb is None:
        return -1, -1
    timeout_s, interval_s = hb[0], hb[1]
    return max(1, int(timeout_s * 1000)), max(1, int(interval_s * 1000))


def run_worker_cycle(lib, plan: SteadyPlan, fd: int, secret: bytes,
                     skip_tags: bytes, req_tag: int, resp_tag: int, hb):
    """One native steady cycle, worker side, sending what
    ``plan.pack(use_arena=True)`` left in the plan's arena views.
    Returns (DONE, result_segments) | (FRAME, tag, payload) |
    (ERR, rc)."""
    c = _c_common(plan)
    b = plan.cache.get("worker")
    if b is None:
        b = {
            "secret": _mkbuf(secret),
            "skip": _mkbuf(skip_tags),
            "nskip": len(skip_tags),
            # Arena views are stable: the send iovec never rebuilds.
            "send_ptrs": (ctypes.c_void_p * plan.nseg)(
                *[v.ctypes.data for v in plan.send_views]),
        }
        plan.cache["worker"] = b
    result = np.empty(sum(plan.seg_nbytes), np.uint8)
    recv_ptrs = (ctypes.c_void_p * plan.nseg)()
    off = 0
    for j, n in enumerate(plan.seg_nbytes):
        recv_ptrs[j] = result[off:off + n].ctypes.data
        off += n
    timeout_ms, interval_ms = _hb_ms(hb)
    dev_buf = _u8p()
    dev_len = ctypes.c_int64()
    dev_tag = ctypes.c_uint8()
    if plan.chunked:
        # Chunked pipelined send: staging holds the full-precision
        # bytes; the C loop casts wire chunks interleaved with the
        # send (one fused cast+HMAC pass when frame auth is armed).
        ch = plan.cache.get("chunked")
        if ch is None:
            ch = {
                "stage_ptrs": (ctypes.c_void_p * plan.nseg)(*[
                    0 if v is None else v.ctypes.data
                    for v in plan.stage_views]),
                "stage_codes": (ctypes.c_int * plan.nseg)(*[
                    -1 if s is None
                    else _native._DTYPE_CODES[str(s)]
                    for s in plan.seg_src_dtypes]),
            }
            plan.cache["chunked"] = ch
        rc = lib.hvd_steady_worker_chunked(
            fd, req_tag, resp_tag, c["prefix"], len(plan.prefix),
            c["hdr_ptrs"], c["hdr_lens"], b["send_ptrs"],
            ch["stage_ptrs"], ch["stage_codes"],
            plan.chunk_bytes, recv_ptrs,
            c["seg_lens"], c["seg_codes"], plan.nseg,
            b["secret"], len(secret),
            b["skip"], b["nskip"], timeout_ms, interval_ms,
            ctypes.byref(dev_buf), ctypes.byref(dev_len),
            ctypes.byref(dev_tag))
    else:
        rc = lib.hvd_steady_worker(
            fd, req_tag, resp_tag, c["prefix"], len(plan.prefix),
            c["hdr_ptrs"], c["hdr_lens"], b["send_ptrs"], recv_ptrs,
            c["seg_lens"], plan.nseg, b["secret"], len(secret),
            b["skip"], b["nskip"], timeout_ms, interval_ms,
            ctypes.byref(dev_buf), ctypes.byref(dev_len),
            ctypes.byref(dev_tag))
    if rc == 0:
        return DONE, plan.result_segments(result)
    if rc == 1:
        try:
            payload = ctypes.string_at(dev_buf, dev_len.value)
        finally:
            lib.hvd_free(dev_buf)
        return FRAME, (dev_tag.value, payload)
    return ERR, rc


def _c_coord(plan: SteadyPlan, n: int, scratch: FusionArena) -> Dict:
    """Coordinator bundle: per-peer scratch segment views + pointer
    table, rebuilt when the peer count or scratch allocation moves."""
    key = ("coord", n, scratch.generation)
    b = plan.cache.get("coord")
    if b is not None and b["key"] == key:
        return b
    per_peer = sum(plan.seg_nbytes)
    scratch.ensure(n * per_peer)
    if scratch.generation != key[2]:
        key = ("coord", n, scratch.generation)
    peer_views: List[List[np.ndarray]] = []
    ptrs = (_u8p * (n * plan.nseg))()
    for i in range(n):
        off = i * per_peer
        segs = []
        for j, (npdt, nb, count) in enumerate(zip(
                plan.seg_np_dtypes, plan.seg_nbytes, plan.seg_counts)):
            v = scratch.typed(off, npdt, count)
            segs.append(v)
            ptrs[i * plan.nseg + j] = ctypes.cast(
                ctypes.c_void_p(v.ctypes.data), _u8p)
            off += nb
        peer_views.append(segs)
    b = {"key": key, "peer_views": peer_views, "peer_ptrs": ptrs}
    plan.cache["coord"] = b
    return b


def run_coord_cycle(lib, plan: SteadyPlan, fds: List[int],
                    secret: bytes, acc_bufs: List[np.ndarray],
                    skip_tags: bytes, req_tag: int, resp_tag: int,
                    hb, on_idle, scratch: FusionArena, on_oob):
    """One native steady cycle, coordinator side. ``acc_bufs`` hold
    rank 0's own packed contribution and are reduced IN PLACE into the
    world sums. ``on_oob(peer_idx, tag, payload) -> bool`` absorbs an
    out-of-band frame (metrics) — True resumes the native gather with
    the already-received frames intact. Returns
    (DONE, (acc segments, arrivals)) | (DEV, (idx, tag, payload,
    done_list, peer_views)) | (ERR, (rc, done_list)). ``arrivals`` is
    each peer's frame-completion stamp on CLOCK_MONOTONIC (0.0 for a
    frame absorbed before this call re-entered, e.g. across an
    out-of-band bounce) — the steady fast path's feed into the
    coordinator's straggler attribution."""
    n = len(fds)
    c = _c_common(plan)
    b = _c_coord(plan, n, scratch)
    # Secret/skip/fd marshalling is step-invariant (fds only change on
    # a dead channel, which the caller re-probes every cycle): cache
    # it like the worker half's bundle instead of re-copying per step.
    io_key = (tuple(fds), skip_tags)
    io = plan.cache.get("coord_io")
    if io is None or io["key"] != io_key:
        io = {"key": io_key, "sec": _mkbuf(secret),
              "skip": _mkbuf(skip_tags),
              "fds": (ctypes.c_int * n)(*fds)}
        plan.cache["coord_io"] = io
    sec = io["sec"]
    skip = io["skip"]
    fds_arr = io["fds"]
    acc_ptrs = (ctypes.c_void_p * plan.nseg)(
        *[a.ctypes.data for a in acc_bufs])
    done = (ctypes.c_uint8 * n)()
    arrive = (ctypes.c_double * n)()
    timeout_ms, interval_ms = _hb_ms(hb)
    idle_cb = on_idle if on_idle is not None else _native.NULL_ON_IDLE
    dev_idx = ctypes.c_int(-1)
    dev_buf = _u8p()
    dev_len = ctypes.c_int64()
    dev_tag = ctypes.c_uint8()
    while True:
        rc = lib.hvd_steady_coord(
            fds_arr, n, req_tag, resp_tag, c["prefix"],
            len(plan.prefix), c["hdr_ptrs"], c["hdr_lens"],
            c["seg_lens"], c["seg_codes"], plan.nseg, b["peer_ptrs"],
            acc_ptrs, sec, len(secret), skip, len(skip_tags),
            timeout_ms, interval_ms, idle_cb, done, arrive,
            ctypes.byref(dev_idx), ctypes.byref(dev_buf),
            ctypes.byref(dev_len), ctypes.byref(dev_tag))
        if rc == 0:
            return DONE, ([(dt, a) for dt, a in
                           zip(plan.seg_dtypes, acc_bufs)],
                          list(arrive))
        if rc == 1:
            try:
                payload = ctypes.string_at(dev_buf, dev_len.value)
            finally:
                lib.hvd_free(dev_buf)
            if on_oob is not None and on_oob(dev_idx.value,
                                            dev_tag.value, payload):
                continue  # absorbed (metrics): resume the gather
            return DEV, (dev_idx.value, dev_tag.value, payload,
                         list(done), b["peer_views"])
        return ERR, (rc, list(done))


def peer_frame_bytes(plan: SteadyPlan, peer_segs) -> bytes:
    """Reconstruct a peer's full CACHED_SPEC frame from its absorbed
    scratch segments — the coordinator's deviation fallback feeds
    these to the classic negotiation path (rare; a transition cycle
    pays one copy)."""
    parts = [plan.prefix]
    for h, v in zip(plan.seg_hdrs, peer_segs):
        parts.append(h)
        parts.append(memoryview(v.view(np.uint8)))
    return b"".join(parts)


# Errno helpers for the controllers' error mapping.
ETIMEDOUT = -errno.ETIMEDOUT
EBADMSG = -errno.EBADMSG
