"""Shared-memory data plane for intra-host worlds.

When every rank lives on one host (one process per TPU chip is the
normal deployment shape), tensors should move through RAM, not through
the loopback TCP stack. The reference does exactly this where it
matters most: ``MPIHierarchicalAllgather`` stages node-local data in an
``MPI_Win_allocate_shared`` window and lets ranks memcpy in and out of
it (reference: horovod/common/ops/mpi_operations.cc:179-329). This
backend is the standalone rendering of that idea: one POSIX shared
memory segment per world, negotiated through the existing TCP control
plane, carrying every collective's payload at memcpy speed.

Layout is fixed per segment generation so concurrent ops can never
alias each other across a cycle boundary:

    [ slot 0 | slot 1 | ... | slot N-1 | out region (N slots wide) ]

with every slot ``stride`` bytes (page-padded to the largest negotiated
payload so far; the segment re-establishes and grows when an op
outgrows it). Invariants that make the sync rounds safe:

  * a rank writes ONLY its own slot, and only at the start of its own
    execute — which is provably after it finished reading the previous
    op's result;
  * the out region is written only between a completed world gather
    (all ranks wrote their slots + stopped reading the previous op)
    and the round that releases readers. Writers per path: the
    coordinator alone on the small-op single-round path; each rank's
    DISJOINT 1/N slice between the two barriers of the large-op
    slice-parallel path; local roots on the hierarchical path;
  * results are copied out of the segment before the op returns, so
    user-visible outputs never alias shared pages.

The segment file is unlinked immediately after the establishment
rendezvous (the mappings keep the memory alive), so no /dev/shm litter
survives a crash. Establishment failure on any rank is agreed
world-wide (``controller.agree``) and degrades every rank to the
socket backend together — same pattern as the ring data plane
(ops/ring.py).
"""

from __future__ import annotations

import json
import mmap
import os
from typing import Optional, Tuple

import numpy as np

from horovod_tpu import native as _native
from horovod_tpu.common import logging as hlog
from horovod_tpu.common import wire_dtype as _wd
from horovod_tpu.common.controller import _my_hostname
from horovod_tpu.common.message import Response, ResponseType
from horovod_tpu.common.status import Status
from horovod_tpu.common.timeline import (
    ACT_MEMCPY_IN_FUSION_BUFFER, ACT_MEMCPY_OUT_FUSION_BUFFER,
)
from horovod_tpu.ops.backend import CollectiveBackend
from horovod_tpu.ops.socket_ops import (
    _allgather_layout, _np_from_bytes, _pack_flat, _pack_fused,
    _restore, _to_numpy, _unpack_allgather, _unpack_fused,
)

_PAGE = 4096
# Same-host allreduces at or above this size split the reduction work
# across ranks (slice-parallel sum) instead of summing on the
# coordinator; below it the single-round coordinator sum wins on
# latency.
_PARALLEL_SUM_BYTES = 1 << 20


def _pad(nbytes: int) -> int:
    return -(-max(nbytes, 1) // _PAGE) * _PAGE


class ShmBackend(CollectiveBackend):
    name = "shm"

    def __init__(self, controller, fallback: CollectiveBackend,
                 config=None, secret: bytes = b""):
        self._ctl = controller
        self._fallback = fallback
        self._map: Optional[mmap.mmap] = None
        self._stride = 0
        self._gen = 0
        self._dead = False
        self._opt_in = True if config is None else config.shm_enabled
        self._zero_copy = True if config is None else config.zero_copy
        # Tenant sub-worlds (common/tenancy.py) namespace their
        # segments: two worlds hosted by ONE process (same pid, same
        # generation counter) must never collide on a segment path —
        # the old pid+gen name did exactly that.
        self._world_id = 0 if config is None \
            else int(getattr(config, "world_id", 0))
        # Persistent pack buffer (common/arena.py): fused steady steps
        # re-pack into the same memory instead of allocating per step.
        # Safe here because every shm result is copied OUT of the
        # segment/accumulators before entries see it.
        from horovod_tpu.common.arena import FusionArena
        self._arena = FusionArena() if self._zero_copy else None
        self._m_regrows = None  # set by attach_metrics
        self._m_twolevel = None
        # Two-level cross-host ring among LOCAL ROOTS (ops/ring.py
        # subset establishment): lazy, once, world-agreed — same
        # pattern as the socket backend's flat ring.
        self._secret = secret
        self._roots_ring = None
        self._roots_ring_tried = False
        self._roots_ok = False  # world-identical after first establish
        # int8 error-feedback residuals for the cross-host leg — the
        # same rank-local compensation the socket plane keeps, so the
        # numerics do not silently depend on the transport.
        self._ef = _wd.ErrorFeedback()
        self._ring_hb = None
        if config is not None and config.heartbeat_timeout_s > 0:
            self._ring_hb = (config.heartbeat_timeout_s,
                             config.heartbeat_interval_s)

    def attach_metrics(self, registry) -> None:
        super().attach_metrics(registry)
        # Each regrow re-establishes the segment world-wide — a climbing
        # count means payload sizes keep outgrowing the stride.
        self._m_regrows = registry.counter(
            "hvd_shm_segment_regrows_total",
            "shared-memory segment re-establishments")
        self._m_twolevel = registry.counter(
            "hvd_ops_twolevel_total",
            "allreduce batches carried by the two-level plane "
            "(intra-host shm reduce, cross-host ring among local "
            "roots, intra-host shm broadcast)")

    def enabled(self, entries, response) -> bool:
        """World-consistent by construction: topology is identical on
        every rank, the coordinator's ALG_* stamp rides the broadcast
        response, and anything that can genuinely fail per host
        (segment creation, /dev/shm itself) is decided inside
        establishment by a world-wide agree() vote."""
        t = getattr(self._ctl, "topology", None)
        if not (self._opt_in and not self._dead and t is not None
                and t.size > 1):
            return False
        if response is not None \
                and response.response_type == ResponseType.ALLREDUCE \
                and response.algorithm in (_wd.ALG_STAR, _wd.ALG_RING):
            # A stamped FLAT algorithm belongs to the socket plane —
            # declining here is what makes the coordinator's verdict
            # (and the autotuner's exploration) actually select it.
            return False
        if t.local_size == t.size:
            return True  # same-host world: every collective
        if not (response is not None
                and response.response_type == ResponseType.ALLREDUCE):
            return False
        if response.algorithm == _wd.ALG_TWOLEVEL:
            # The two-level plane serves ANY multi-host world (an
            # all-solo-hosts topology degenerates to the roots ring —
            # still hierarchical bookkeeping, no local legs).
            return True
        # Default routing: the hierarchical local-reduce -> cross ->
        # local-broadcast path, worthwhile when at least one host runs
        # several ranks.
        return max(t.local_sizes) > 1

    @property
    def _hier(self) -> bool:
        t = self._ctl.topology
        return t.local_size < t.size

    # -- segment lifecycle -------------------------------------------------

    def _segment_for(self, nbytes: int) -> Optional[Tuple[mmap.mmap, int]]:
        """Return (mmap, stride) able to hold one ``nbytes`` payload per
        LOCAL slot, (re)establishing through the control plane when the
        current segment is too small. All ranks call this at the same
        negotiated response position with the same ``nbytes``.

        One segment per HOST, created by that host's local root and
        advertised through a hostname-keyed path map broadcast by the
        coordinator (a same-host world is the one-host special case).
        """
        stride = _pad(nbytes)
        solo = self._hier and self._ctl.topology.local_size == 1
        if self._stride >= stride and (self._map is not None or solo):
            return self._map, self._stride
        ctl = self._ctl
        t = ctl.topology
        # Grow generously so streams of slightly-increasing sizes don't
        # re-establish every op.
        stride = _pad(max(stride, 2 * self._stride))
        total = stride * t.local_size * 2
        self._gen += 1
        if self._m_regrows is not None:
            self._m_regrows.inc()
        my_host = _my_hostname()
        new_map = None
        path = ""
        ok = True
        if t.local_rank == 0 and not solo:
            path = (f"/dev/shm/hvdtpu-{os.getpid()}"
                    f"-w{self._world_id:x}-{self._gen}")
            try:
                fd = os.open(path, os.O_CREAT | os.O_RDWR | os.O_EXCL,
                             0o600)
                try:
                    os.ftruncate(fd, total)
                    new_map = mmap.mmap(fd, total)
                finally:
                    os.close(fd)
            except OSError as e:
                hlog.warning(f"shm segment create failed: {e!r}",
                             rank=ctl.rank)
                path, ok = "", False
            payload = json.dumps(
                {"host": my_host, "path": path, "total": total}).encode()
        else:
            payload = b""
        gathered = ctl.gather_data(payload)
        if gathered is not None:  # coordinator
            host_map = {}
            for data in gathered:
                if len(data):
                    info = json.loads(bytes(data).decode())
                    host_map[info["host"]] = (info["path"],
                                              info["total"])
            blob = ctl.broadcast_data(json.dumps(host_map).encode())
        else:
            blob = ctl.broadcast_data(None)
        if new_map is None and not solo:
            # non-creators open their host's segment (solo hier hosts
            # need no segment: there is nobody to share with)
            host_map = json.loads(bytes(blob).decode())
            entry = host_map.get(my_host, ("", 0))
            if entry[0]:
                try:
                    fd = os.open(entry[0], os.O_RDWR)
                    try:
                        new_map = mmap.mmap(fd, entry[1])
                    finally:
                        os.close(fd)
                except OSError as e:
                    hlog.warning(
                        f"shm segment open failed: {e!r}", rank=ctl.rank)
                    ok = False
            else:
                ok = False
        agreed = ctl.agree(ok)
        if path:
            # Every local rank holds a mapping (or we are tearing
            # down); the name can go away now — crash-safe cleanup.
            try:
                os.unlink(path)
            except OSError:
                pass
        if not agreed:
            for m in (new_map, self._map):
                if m is not None:
                    try:
                        m.close()
                    except (BufferError, ValueError):
                        pass
            self._map = None
            self._dead = True
            hlog.warning("shm data plane unavailable; falling back to "
                         "the socket backend", rank=ctl.rank)
            return None
        old = self._map
        self._map = new_map
        self._stride = stride
        if old is not None:
            # Rendezvous above was a barrier: nobody still reads old.
            try:
                old.close()
            except (BufferError, ValueError):
                pass
        return self._map, self._stride

    def _world_barrier(self) -> None:
        # the socket backend's empty gather/broadcast round IS a world
        # barrier; one implementation serves both uses
        self._fallback.execute_barrier((), None)

    def _view(self, offset: int, dtype, count: int) -> np.ndarray:
        return np.frombuffer(self._map, dtype=dtype, count=count,
                             offset=offset)

    def _sum_slots(self, acc: np.ndarray, ranks, stride: int, dtype,
                   count: int, lo: int = 0) -> None:
        """acc += sum of slot[r][lo:lo+len(acc)] for r in ranks (native
        kernel with numpy fallback) — the one accumulation loop every
        reduction path shares."""
        for r in ranks:
            src = self._view(r * stride, dtype, count)[lo:lo + acc.size]
            if not _native.sum_into(acc, src):
                acc += src

    def close(self) -> None:
        if self._roots_ring is not None:
            try:
                self._roots_ring.close()
            except Exception:
                pass
            self._roots_ring = None
        if self._map is not None:
            try:
                self._map.close()
            except (BufferError, ValueError):
                pass
            self._map = None

    # -- collectives ---------------------------------------------------------

    def execute_allreduce(self, entries, response: Response) -> Status:
        ctl = self._ctl
        arrays = [_to_numpy(e.tensor) for e in entries]
        dtype = arrays[0].dtype
        names = [e.tensor_name for e in entries]
        multi = len(entries) > 1  # single-tensor pack is a view
        with self.activity(names, ACT_MEMCPY_IN_FUSION_BUFFER,
                           multi) as sp:
            # Arena-safe: every shm result is copied out of the
            # segment before entries see it, so outputs never alias
            # the pack buffer.
            fused, _ = _pack_fused(arrays, response, self._arena)
            sp.nbytes = fused.nbytes
        if fused.size == 0:
            # Nothing to move; every rank short-circuits identically
            # (sizes are negotiated), so no control rounds are owed.
            _unpack_fused(entries, arrays, np.empty(0, dtype=dtype),
                          response)
            return Status.OK()
        seg = self._segment_for(fused.nbytes)
        if seg is None:
            return self._fallback.execute_allreduce(entries, response)
        _, stride = seg
        if self._hier:
            result = self._hier_allreduce(fused, dtype, stride,
                                          response)
        elif fused.nbytes >= _PARALLEL_SUM_BYTES:
            result = self._parallel_sum_allreduce(fused, dtype, stride)
        else:
            out_off = ctl.size * stride
            if ctl.is_coordinator:
                ctl.gather_data(b"")  # all slots written
                out = self._view(out_off, dtype, fused.size)
                out[:] = fused
                self._sum_slots(out, range(1, ctl.size), stride, dtype,
                                fused.size)
                ctl.broadcast_data(b"")
                result = out.copy()
            else:
                slot = self._view(ctl.rank * stride, dtype, fused.size)
                slot[:] = fused
                ctl.gather_data(b"")
                ctl.broadcast_data(None)
                result = self._view(out_off, dtype, fused.size).copy()
        with self.activity(names, ACT_MEMCPY_OUT_FUSION_BUFFER, multi,
                           result.nbytes):
            _unpack_fused(entries, arrays, result, response)
        return Status.OK()

    def _parallel_sum_allreduce(self, fused: np.ndarray, dtype,
                                stride: int) -> np.ndarray:
        """Large-payload same-host allreduce with the REDUCTION work
        split across ranks: every rank writes its slot, then sums its
        1/N slice of all slots into the out region (the reduce-scatter
        + all-gather of a ring, rendered on shared memory). Costs one
        extra sync round vs the coordinator-sum path but divides the
        sum's memory-bandwidth load N ways — the same reason the
        reference's hierarchical ops spread work over ranks."""
        ctl = self._ctl
        size = ctl.size
        out_off = size * stride
        slot = self._view(ctl.rank * stride, dtype, fused.size)
        slot[:] = fused
        self._world_barrier()  # round A: all slots written
        # exact integer split: contiguous, gap-free, overlap-free
        lo = ctl.rank * fused.size // size
        hi = (ctl.rank + 1) * fused.size // size
        if hi > lo:
            out = self._view(out_off, dtype, fused.size)
            acc = out[lo:hi]
            acc[:] = self._view(0, dtype, fused.size)[lo:hi]
            self._sum_slots(acc, range(1, size), stride, dtype,
                            fused.size, lo=lo)
        self._world_barrier()  # round B: every slice summed
        return self._view(out_off, dtype, fused.size).copy()

    def _roots_ring_for(self):
        """Cross-host ring among LOCAL ROOTS — the two-level plane's
        middle leg. Established lazily, ONCE, at a world-consistent
        response position (every rank runs the rendezvous control
        rounds; only roots open links). Non-roots get None even on
        success, so one extra one-time agree() round publishes the
        verdict to them — ``self._roots_ok`` is world-identical after
        the first call."""
        if not self._roots_ring_tried:
            self._roots_ring_tried = True
            roots = list(self._ctl.topology.local_roots)
            from horovod_tpu.ops import ring as _ring
            self._roots_ring = _ring.establish(
                self._ctl, self._secret, hb=self._ring_hb,
                members=roots)
            member = self._ctl.rank in roots
            self._roots_ok = self._ctl.agree(
                (self._roots_ring is not None) if member else True)
        return self._roots_ring

    def _cross_exchange_star(self, acc, dtype, wire: int,
                             count: int, key: tuple):
        """Cross-host leg, star shape: roots funnel their host sums
        through the coordinator (compressed at the negotiated wire
        dtype), everyone else rides the rounds with empty payloads so
        the protocol stays size-independent. Returns the f32 world sum
        on roots, None elsewhere."""
        from horovod_tpu.ops import socket_ops as _sops
        ctl = self._ctl
        t = ctl.topology
        lr = t.local_rank
        wire_nbytes = _wd.compressed_nbytes(
            wire, count, dtype.itemsize) if wire else 0
        if lr == 0:
            # ONE shared compress-leg implementation with the socket
            # plane (cast/quantize + error feedback + saved/ratio
            # metrics), so the transports can never drift on numerics
            # or accounting.
            payload = _sops.compress_send_payload(
                acc, wire, self._ef, key) if wire else acc
        else:
            payload = b""
        gathered = ctl.gather_data(payload)  # round 2a
        # Root membership comes from the topology, not payload lengths,
        # so the protocol is size-independent.
        roots = set(t.local_roots)
        if gathered is not None:  # coordinator (always a local root)
            peers = [gathered[r] for r in range(1, ctl.size)
                     if r in roots]
            if wire:
                from horovod_tpu.common.network import as_byte_view
                out_buf = _wd.reduce_wire(payload, peers, wire,
                                          dtype, count)
                blob = as_byte_view(out_buf)
                total = _wd.decompress(out_buf, wire, dtype, count)
            else:
                total = payload  # acc, fresh
                for p in peers:
                    src = np.frombuffer(p, dtype=dtype)
                    if not _native.sum_into(total, src):
                        total += src
                blob = memoryview(total).cast("B")
            payloads = [blob if r in roots else b""
                        for r in range(ctl.size)]
            payloads[0] = b""  # our own copy is ``total`` already
            ctl.scatter_data(payloads)  # round 2b
            return total
        if self._zero_copy:
            # Roots receive the world sum straight into a fresh array;
            # non-roots' empty slice costs nothing.
            if wire == _wd.WIRE_INT8:
                flat = np.empty(wire_nbytes if lr == 0 else 0,
                                np.uint8)
            elif wire:
                flat = np.empty(count if lr == 0 else 0,
                                _wd.wire_np_dtype(wire))
            else:
                flat = np.empty(count if lr == 0 else 0, dtype)
            ctl.scatter_data_into(None, flat)  # round 2b
            if lr != 0:
                return None
            return _wd.decompress(flat, wire, dtype, count) \
                if wire else flat
        data = ctl.scatter_data(None)  # round 2b
        if lr != 0:
            return None
        if wire:
            return _wd.decompress(data, wire, dtype, count)
        return _np_from_bytes(data, dtype)

    def _hier_allreduce(self, fused: np.ndarray, dtype,
                        stride: int, response: Response) -> np.ndarray:
        """Multi-host allreduce: local shm reduce -> cross-host
        exchange among LOCAL ROOTS only -> local shm broadcast. The
        exact decomposition of the reference's
        ``NCCLHierarchicalAllreduce`` (nccl_operations.cc:167-372:
        intra-node reduce, inter-node exchange on one participant per
        node, intra-node broadcast), with cross-host bytes cut from
        N*S to K*S for K hosts — and cut AGAIN by the negotiated wire
        dtype, applied only to the cross-host leg (intra-host legs
        move through RAM, where a cast costs more than it saves).

        The cross leg has two shapes, selected by the coordinator's
        ALG_* stamp: the classic star through rank 0 (default), or —
        ``ALG_TWOLEVEL`` — a reduce-scatter/allgather ring among the
        local roots (ops/ring.py subset ring), whose per-root wire
        bytes are 2·S·(K-1)/K instead of the star root's 2·S·(K-1).

        Control rounds, identical on every rank:
          1. barrier — all local slots written;
          2. cross leg (star: gather+scatter rounds; ring: root-to-
             root links only — no world rounds);
          3. barrier — out regions written; locals read.
        """
        ctl = self._ctl
        t = ctl.topology
        lr, ls = t.local_rank, t.local_size
        out_off = ls * stride

        if lr != 0:
            slot = self._view(lr * stride, dtype, fused.size)
            slot[:] = fused
        self._world_barrier()  # round 1: every host's slots complete

        acc = None
        if lr == 0:
            acc = np.array(fused, dtype=dtype, copy=True)
            self._sum_slots(acc, range(1, ls), stride, dtype,
                            fused.size)

        wire = response.wire_dtype \
            if _wd.is_floating(dtype) else _wd.WIRE_NONE
        twolevel = response.algorithm == _wd.ALG_TWOLEVEL
        if twolevel:
            # Every rank reaches this establishment point for the same
            # response, so the rendezvous rounds stay world-aligned;
            # an unestablishable ring degrades every rank to the star
            # exchange together (world-agreed vote).
            ring = self._roots_ring_for()
            twolevel = self._roots_ok
        if twolevel:
            if self._m_twolevel is not None:
                self._m_twolevel.inc()
            result = None
            if lr == 0:
                wire = _wd.ring_wire(wire)
                if wire:
                    from horovod_tpu.ops import socket_ops as _sops
                    wbuf = _sops.compress_send_payload(acc, wire)
                    ring.allreduce_(wbuf)
                    result = _wd.decompress(wbuf, wire, dtype,
                                            fused.size)
                else:
                    result = ring.allreduce_(acc)
        else:
            result = self._cross_exchange_star(
                acc, dtype, wire, fused.size,
                tuple(response.tensor_names))

        if lr == 0 and ls > 1:
            # solo hosts have no readers — skip the out-region copy
            out = self._view(out_off, dtype, fused.size)
            out[:] = result
        self._world_barrier()  # round 3: out regions complete
        if lr != 0:
            result = self._view(out_off, dtype, fused.size).copy()
        return result

    def execute_allgather(self, entries, response: Response) -> Status:
        ctl = self._ctl
        arrays = [np.ascontiguousarray(_to_numpy(e.tensor))
                  for e in entries]
        names = [e.tensor_name for e in entries]
        comp, rank_counts = _allgather_layout(entries, arrays, response,
                                              ctl.size)
        itemsize = arrays[0].dtype.itemsize
        seg = self._segment_for(max(rank_counts) * itemsize)
        if seg is None:
            return self._fallback.execute_allgather(entries, response)
        _, stride = seg
        out_off = ctl.size * stride
        total_elems = sum(rank_counts)
        multi = len(entries) > 1
        with self.activity(names, ACT_MEMCPY_IN_FUSION_BUFFER,
                           multi) as sp:
            packed = _pack_flat(arrays, self._arena)
            sp.nbytes = packed.nbytes
        dtype = packed.dtype
        if ctl.is_coordinator:
            ctl.gather_data(b"")
            out = self._view(out_off, dtype, total_elems)
            pos = 0
            for r in range(ctl.size):
                n = rank_counts[r]
                if r == 0:
                    out[pos:pos + n] = packed
                else:
                    out[pos:pos + n] = self._view(r * stride, dtype, n)
                pos += n
            ctl.broadcast_data(b"")
            result = out.copy()
        else:
            slot = self._view(ctl.rank * stride, dtype, packed.size)
            slot[:] = packed
            ctl.gather_data(b"")
            ctl.broadcast_data(None)
            result = self._view(out_off, dtype, total_elems).copy()
        with self.activity(names, ACT_MEMCPY_OUT_FUSION_BUFFER, multi,
                           result.nbytes):
            _unpack_allgather(entries, arrays, result, comp,
                              rank_counts)
        return Status.OK()

    def execute_broadcast(self, entries, response: Response) -> Status:
        ctl = self._ctl
        (entry,) = entries
        orig = _to_numpy(entry.tensor)
        arr = np.ascontiguousarray(orig)
        seg = self._segment_for(arr.nbytes)
        if seg is None:
            return self._fallback.execute_broadcast(entries, response)
        _, stride = seg
        out_off = ctl.size * stride
        root = entry.root_rank
        if ctl.rank == root and not ctl.is_coordinator:
            slot = self._view(ctl.rank * stride, arr.dtype, arr.size)
            slot[:] = arr.reshape(-1)
        if ctl.is_coordinator:
            ctl.gather_data(b"")
            out = self._view(out_off, arr.dtype, arr.size)
            if root == 0:
                out[:] = arr.reshape(-1)
            else:
                out[:] = self._view(root * stride, arr.dtype, arr.size)
            ctl.broadcast_data(b"")
        else:
            ctl.gather_data(b"")
            ctl.broadcast_data(None)
        result = self._view(out_off, arr.dtype, arr.size).copy()
        entry.output = _restore(entry, result.reshape(orig.shape))
        return Status.OK()

    def execute_alltoall(self, entries, response: Response) -> Status:
        ctl = self._ctl
        (entry,) = entries
        arr = np.ascontiguousarray(_to_numpy(entry.tensor))
        seg = self._segment_for(arr.nbytes)
        if seg is None:
            return self._fallback.execute_alltoall(entries, response)
        _, stride = seg
        size = ctl.size
        out_off = size * stride
        per_elems = (arr.shape[0] // size) * (
            int(np.prod(arr.shape[1:], dtype=np.int64))
            if arr.ndim > 1 else 1)
        if ctl.is_coordinator:
            ctl.gather_data(b"")
            flat0 = arr.reshape(-1)
            # destination d's block lands at out_off + d*stride, source
            # blocks concatenated in rank order.
            for d in range(size):
                dst = self._view(out_off + d * stride, arr.dtype,
                                 size * per_elems)
                for s in range(size):
                    blk = (flat0[d * per_elems:(d + 1) * per_elems]
                           if s == 0 else
                           self._view(s * stride, arr.dtype,
                                      arr.size)[d * per_elems:
                                                (d + 1) * per_elems])
                    dst[s * per_elems:(s + 1) * per_elems] = blk
            ctl.broadcast_data(b"")
        else:
            slot = self._view(ctl.rank * stride, arr.dtype, arr.size)
            slot[:] = arr.reshape(-1)
            ctl.gather_data(b"")
            ctl.broadcast_data(None)
        result = self._view(out_off + ctl.rank * stride, arr.dtype,
                            size * per_elems).copy()
        entry.output = _restore(entry, result.reshape(arr.shape))
        return Status.OK()

    def execute_reducescatter(self, entries, response: Response) -> Status:
        ctl = self._ctl
        (entry,) = entries
        arr = np.ascontiguousarray(_to_numpy(entry.tensor))
        if response.prescale_factor != 1.0:
            arr = arr * np.asarray(response.prescale_factor, arr.dtype)
        seg = self._segment_for(arr.nbytes)
        if seg is None:
            return self._fallback.execute_reducescatter(entries, response)
        _, stride = seg
        size = ctl.size
        out_off = size * stride
        per_rank = arr.shape[0] // size
        per_elems = per_rank * (int(np.prod(arr.shape[1:],
                                            dtype=np.int64))
                                if arr.ndim > 1 else 1)
        if ctl.is_coordinator:
            ctl.gather_data(b"")
            out = self._view(out_off, arr.dtype, arr.size)
            out[:] = arr.reshape(-1)
            self._sum_slots(out, range(1, size), stride, arr.dtype,
                            arr.size)
            ctl.broadcast_data(b"")
        else:
            slot = self._view(ctl.rank * stride, arr.dtype, arr.size)
            slot[:] = arr.reshape(-1)
            ctl.gather_data(b"")
            ctl.broadcast_data(None)
        result = self._view(out_off + ctl.rank * per_elems *
                            arr.dtype.itemsize, arr.dtype,
                            per_elems).copy()
        result = result.reshape((per_rank,) + arr.shape[1:])
        if response.postscale_factor != 1.0:
            result = result * np.asarray(response.postscale_factor,
                                         arr.dtype)
        entry.output = _restore(entry, result)
        return Status.OK()

    def execute_barrier(self, entries, response: Response) -> Status:
        # A barrier moves no payload; the socket backend's tiny
        # gather/broadcast round IS the barrier.
        return self._fallback.execute_barrier(entries, response)
