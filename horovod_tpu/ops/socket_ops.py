"""TCP socket collective backend — the universal host fallback.

Role-equivalent of the reference's MPI CPU ops
(reference: horovod/common/ops/mpi_operations.cc — ``MPIAllreduce``
25-84, ``MPIAllgather`` 95-173, ``MPIBroadcast`` 334-358), which are the
always-enabled last resort in the op priority list. A TPU host has no
MPI; this backend runs the same collectives over the controller's
persistent TCP channels with a star topology (gather → combine at rank 0
→ broadcast/scatter).

Payloads are numpy buffers; jax arrays are staged through host memory
here, exactly like the reference's *CudaOnCPU staging path
(reference: horovod/torch/mpi_ops_v2.cc:78-111). The XLA mesh backend
(xla_ops.py) outranks this one whenever a multi-process JAX world
exists, keeping the data plane on ICI/DCN.

Fused allreduce packs all entries into one contiguous buffer before the
wire round-trip — the fusion-buffer pack/unpack of the reference
(reference: ops/collective_operations.cc:35-63) — so a fused batch costs
one gather+broadcast regardless of tensor count.
"""

from __future__ import annotations

from typing import List

import numpy as np

from horovod_tpu import native as _native
from horovod_tpu.common import wire_dtype as _wd
from horovod_tpu.common.arena import FusionArena, concat_into
from horovod_tpu.common.controller import Controller
from horovod_tpu.common.message import (
    Response, datatype_to_numpy_dtype, numpy_dtype_to_datatype,
)
from horovod_tpu.common.metrics import NOOP_METRIC
from horovod_tpu.common.status import Status
from horovod_tpu.common.timeline import (
    ACT_MEMCPY_IN_FUSION_BUFFER, ACT_MEMCPY_OUT_FUSION_BUFFER,
)
from horovod_tpu.ops.backend import CollectiveBackend

# Fallback-copy observability (hvd_data_copies_total, shared with the
# runtime's counter by registry name-memoization): every defensive
# byte-object copy the zero-copy plane exists to delete ticks it, so
# "is the zero-copy path engaged" is one metrics read. NOOP when
# metrics are off/unattached.
_COPY_METRIC = NOOP_METRIC

# Wire-compression observability, shared by name with the runtime's
# counters: bytes this rank did NOT put on the wire thanks to the
# negotiated wire dtype, and the per-op compression ratio.
_SAVED_METRIC = NOOP_METRIC
_RATIO_METRIC = NOOP_METRIC


def _to_numpy(tensor) -> np.ndarray:
    if isinstance(tensor, np.ndarray):
        return tensor
    return np.asarray(tensor)


def record_compression(src_nbytes: int, wire_nbytes: int) -> None:
    """THE one wire-compression accounting site (saved bytes +
    ratio): every compress leg — the backends via
    compress_send_payload, the runtime's spec/native steady packs —
    ticks through here, so the metric semantics can never drift
    between planes."""
    _SAVED_METRIC.inc(max(0, src_nbytes - wire_nbytes))
    _RATIO_METRIC.observe(wire_nbytes / max(1, src_nbytes))


def compress_send_payload(arr: np.ndarray, wire: int, ef=None,
                          key: tuple = None,
                          out: np.ndarray = None) -> np.ndarray:
    """THE one compress-leg implementation every host plane shares:
    wire-cast (into ``out`` — an arena view — when given) or int8
    quantize with error feedback, plus the saved-bytes/ratio metrics.
    One call per payload per op, so the counters stay exact however
    many planes reuse it."""
    record_compression(
        arr.nbytes,
        _wd.compressed_nbytes(wire, arr.size, arr.dtype.itemsize))
    if wire == _wd.WIRE_INT8:
        if ef is not None:
            # Fused native pass: compensate + quantize + next-step
            # residual in one sweep (falls back to the classic
            # apply -> quantize -> update triple, bit-identically).
            return _wd.quantize_ef(arr, ef, key)
        return _wd.quantize(arr)
    if out is not None:
        _wd.cast_into(arr, out)
        return out
    return arr.astype(_wd.wire_np_dtype(wire))  # fresh + writable


def _np_from_bytes(data: bytes, dtype) -> np.ndarray:
    """Writable array over received bytes. A bare ``np.frombuffer`` over
    ``bytes`` is read-only and would poison outputs (callers expect
    writable tensors, like the reference's allocated outputs). This IS
    the defensive copy the zero-copy recv-into paths delete — counted,
    so the fallback tier is visible on the metrics plane."""
    _COPY_METRIC.inc()
    return np.frombuffer(bytearray(data), dtype=dtype)


def _restore(entry, host_result: np.ndarray):
    """Return the result in the entry's native flavor (jax in → jax out)."""
    if entry.context == "jax":
        import jax
        return jax.device_put(host_result)
    return host_result


def _pack_fused(arrays: List[np.ndarray], response: Response,
                arena: FusionArena = None):
    """Fusion-buffer pack shared by the host backends (reference:
    ops/collective_operations.cc:35-63). Returns (flat, fresh): ``fresh``
    is True when ``flat`` is known not to alias a caller tensor (safe to
    mutate in place). Single-tensor packs skip the copy, like the
    reference's MPI_IN_PLACE path (mpi_operations.cc:44-47). With an
    ``arena``, multi-tensor packs land in the persistent buffer
    instead of a per-step allocation — callers must then guarantee
    user-visible outputs never alias ``flat`` (see common/arena.py)."""
    dtype = arrays[0].dtype
    fresh = len(arrays) > 1
    flat = _pack_flat(arrays, arena)
    if response.prescale_factor != 1.0:
        if fresh and arena is not None and flat.flags.writeable:
            np.multiply(flat, np.asarray(response.prescale_factor,
                                         dtype), out=flat)
        else:
            flat = flat * np.asarray(response.prescale_factor, dtype)
        fresh = True
    return flat, fresh


def _allgather_layout(entries, arrays, response: Response, size: int):
    """Displacement math for a (possibly fused) allgather response
    (reference: AllgatherOp::AllocateOutput / SetEntryComponentOffsets,
    ops/collective_operations.cc:68-134). ``response.tensor_sizes`` is
    entry-major: sizes[ec * size + rc] = entry ec's dim-0 rows from
    rank rc. Returns (comp, rank_counts):
    comp[ec][rc] = elements entry ec contributes from rank rc;
    rank_counts[rc] = total elements in rank rc's packed block."""
    sizes = response.tensor_sizes
    comp = []
    for ec, a in enumerate(arrays):
        row = int(np.prod(a.shape[1:], dtype=np.int64)) \
            if a.ndim > 1 else 1
        comp.append([sizes[ec * size + rc] * row for rc in range(size)])
    rank_counts = [sum(comp[ec][rc] for ec in range(len(arrays)))
                   for rc in range(size)]
    return comp, rank_counts


def _pack_flat(arrays: List[np.ndarray],
               arena: FusionArena = None) -> np.ndarray:
    """Flatten + concatenate same-dtype tensors into one fused buffer
    (the reference's MemcpyInFusionBuffer for allreduce,
    collective_operations.cc:35-63, and for allgather — entry order —
    collective_operations.cc:136-150): the native one-call pack when
    available, numpy concatenation otherwise. Single-tensor packs stay
    a view. With an ``arena`` (and uniform dtypes) the pack reuses the
    persistent buffer — the reference's long-lived fusion buffer —
    instead of allocating per step. The one helper both host planes'
    allreduce AND allgather pack paths share."""
    if len(arrays) == 1:
        return np.ascontiguousarray(arrays[0]).reshape(-1)
    flats = [np.ascontiguousarray(a).reshape(-1) for a in arrays]
    if arena is not None:
        dtype = flats[0].dtype
        if all(a.dtype == dtype for a in flats):
            total = sum(a.size for a in flats)
            dst = arena.typed(0, dtype, total)
            concat_into(flats, dst)
            return dst
    packed = _native.pack(flats)
    return packed if packed is not None else np.concatenate(flats)


def _unpack_allgather(entries, arrays, result: np.ndarray, comp,
                      rank_counts) -> None:
    """Per-entry unpack of the rank-major gathered buffer: entry ec's
    output is the concatenation over ranks of its component inside each
    rank's block (the reference's allgather MemcpyOutFusionBuffer,
    collective_operations.cc:152-168)."""
    size = len(rank_counts)
    rank_off = [0] * size
    for rc in range(1, size):
        rank_off[rc] = rank_off[rc - 1] + rank_counts[rc - 1]
    # entry_off[rc]: running offset of the NEXT entry's component
    # inside rank rc's block — O(entries x ranks) total, not O(E^2 N).
    entry_off = list(rank_off)
    for ec, (e, a) in enumerate(zip(entries, arrays)):
        parts = []
        for rc in range(size):
            off = entry_off[rc]
            parts.append(result[off:off + comp[ec][rc]])
            entry_off[rc] = off + comp[ec][rc]
        flat = parts[0] if size == 1 else np.concatenate(parts)
        total_rows = sum(comp[ec]) // (
            int(np.prod(a.shape[1:], dtype=np.int64)) if a.ndim > 1
            else 1)
        e.output = _restore(e, flat.reshape((total_rows,) + a.shape[1:]))


def _unpack_fused(entries, arrays, result: np.ndarray, response: Response):
    """Per-entry unpack of a fused result + postscale (the reference's
    MemcpyOutFusionBuffer, collective_operations.cc:35-63). ``result``
    must be safe for entries to alias (fresh or already copied)."""
    if response.postscale_factor != 1.0:
        factor = np.asarray(response.postscale_factor, result.dtype)
        if result.flags.writeable:
            # postscale (the averaging hot path) in place: every caller
            # hands a fresh buffer, so this saves a payload-size copy
            np.multiply(result, factor, out=result)
        else:
            result = result * factor
    offset = 0
    for e, a in zip(entries, arrays):
        n = a.size
        e.output = _restore(e, result[offset:offset + n].reshape(a.shape))
        offset += n


class SocketBackend(CollectiveBackend):
    name = "socket"

    # Metrics defaults for never-attached (metrics-off) backends.
    _m_star_ops = NOOP_METRIC
    _m_ring_ops = NOOP_METRIC
    _m_ring_link_bytes = None

    def __init__(self, controller: Controller, secret: bytes = b"",
                 config=None):
        from horovod_tpu.common.config import Config
        cfg = config or Config()
        self._ctl = controller
        self._secret = secret
        self._ring = None
        self._ring_tried = False
        self._ring_threshold = cfg.ring_threshold_bytes
        # Zero-copy plane (HOROVOD_TPU_ZERO_COPY): pack into the
        # persistent fusion arena, receive into preallocated arrays.
        # Off restores the PR 3 byte-copy paths verbatim (the
        # collective_bench A/B lever).
        self._zero_copy = cfg.zero_copy
        self._arena = FusionArena()         # send-side pack buffer
        self._gather_arena = FusionArena()  # coordinator peer scratch
        # Liveness deadline for the worker↔worker ring channels (same
        # knobs as the control plane; None when detection is disabled).
        self._ring_hb = ((cfg.heartbeat_timeout_s,
                          cfg.heartbeat_interval_s)
                         if cfg.heartbeat_timeout_s > 0 else None)
        # Wire-compression state: a dedicated arena for compressed
        # send payloads (the f32 pack arena keeps its layout) and the
        # int8 error-feedback residual store (rank-local by design —
        # each rank compensates its OWN quantization error).
        self._wire_arena = FusionArena()
        self._ef = _wd.ErrorFeedback()

    def enabled(self, entries, response) -> bool:
        return self._ctl.size > 1

    def attach_metrics(self, registry) -> None:
        super().attach_metrics(registry)
        # Which route the negotiated size picked — the live answer to
        # "are my payloads riding the ring or funneling through the
        # star?" (docs/metrics.md).
        self._m_star_ops = registry.counter(
            'hvd_socket_path_ops_total{path="star"}')
        self._m_ring_ops = registry.counter(
            'hvd_socket_path_ops_total{path="ring"}')
        self._m_ring_link_bytes = registry.counter(
            "hvd_ring_link_bytes_total",
            "bytes this rank shipped over its ring link")
        # Same counter object as the runtime's (registry memoizes by
        # name): the module-level hook lets _np_from_bytes count from
        # shared helpers without threading a backend through.
        global _COPY_METRIC, _SAVED_METRIC, _RATIO_METRIC
        _COPY_METRIC = registry.counter(
            "hvd_data_copies_total",
            "payload byte-object copies on fallback data paths "
            "(0 while the zero-copy plane is engaged)")
        from horovod_tpu.common.metrics import RATIO_BUCKETS
        _SAVED_METRIC = registry.counter(
            "hvd_wire_bytes_saved_total",
            "payload bytes kept OFF the wire by the negotiated "
            "wire dtype (uncompressed minus wire size, per send)")
        _RATIO_METRIC = registry.histogram(
            "hvd_compression_ratio",
            "wire bytes / uncompressed bytes per compressed payload",
            RATIO_BUCKETS)
        # The int8 codec's numpy fallback legs tick the same copy
        # counter from inside wire_dtype (the native codec ticks
        # nothing — that's the point).
        _wd.attach_copy_counter(_COPY_METRIC)

    def fused_cycle_reducible(self, nbytes: int) -> bool:
        """Star-bound batches (below the ring threshold) already move
        through the coordinator's channels — exactly what the
        speculative fused cycle inlines. Mirrors _ring_for's routing
        WITHOUT establishing the ring (a probe must stay passive)."""
        return self._ctl.size > 1 and (
            self._ring_threshold < 0 or nbytes < self._ring_threshold
            or self._ctl.size < 3)

    def close(self) -> None:
        if self._ring is not None:
            self._ring.close()
            self._ring = None

    def _ring_for(self, nbytes: int, algorithm: int = 0):
        """Ring data plane for large payloads: establish lazily, once,
        at a world-consistent response position (all ranks evaluate the
        same negotiated size against the same threshold — and the same
        coordinator-stamped ALG_* verdict). None => star. A stamped
        ALG_STAR/ALG_RING overrides the size heuristic; an
        unestablishable forced ring degrades to the star on every rank
        together (the establishment vote is world-agreed)."""
        if algorithm == _wd.ALG_STAR:
            return None
        # HOROVOD_TPU_RING_THRESHOLD=-1 is an explicit operator
        # opt-out (firewalled inter-rank dials, broken fabric): a
        # stamped ALG_RING must not override it with a surprise
        # rendezvous — the world degrades to the star together.
        forced = (algorithm == _wd.ALG_RING and self._ctl.size >= 2
                  and self._ring_threshold >= 0)
        if not forced and (
                self._ring_threshold < 0 or nbytes < self._ring_threshold
                or self._ctl.size < 3):
            return None
        if not self._ring_tried:
            self._ring_tried = True
            from horovod_tpu.ops import ring as _ring
            self._ring = _ring.establish(self._ctl, self._secret,
                                         hb=self._ring_hb)
            # Capture the rebindable metric hook once: a metrics-plane
            # re-registration between the None test and the use would
            # hand the ring a half-initialized counter.
            m_link = self._m_ring_link_bytes
            if self._ring is not None and m_link is not None:
                self._ring.m_link_bytes = m_link
        return self._ring

    # -- allreduce -------------------------------------------------------
    def execute_allreduce(self, entries, response: Response) -> Status:
        ctl = self._ctl
        arrays = [_to_numpy(e.tensor) for e in entries]
        dtype = arrays[0].dtype
        names = [e.tensor_name for e in entries]
        multi = len(entries) > 1  # single-tensor pack is a view
        nbytes = sum(a.nbytes for a in arrays)
        # Route BEFORE packing: large payloads ride the ring (every
        # rank computes the same negotiated size against the same
        # threshold AND the same coordinator-stamped algorithm, so the
        # path choice is world-consistent). Routing uses UNCOMPRESSED
        # bytes on purpose — the wire dtype must not flip the route.
        ring = self._ring_for(nbytes, response.algorithm)
        # Arena packing only for batches that actually stay off the
        # ring: the uncompressed ring mutates its buffer in place AND
        # returns it as the result, so a ring-bound pack must stay a
        # per-op buffer outputs may alias — a size heuristic alone is
        # not enough, because a stamped ALG_RING (the autotuner
        # exploring) forces small batches onto the ring too, and an
        # arena-aliased output is then silently overwritten by the
        # next op's pack.
        use_arena = self._zero_copy and ring is None
        with self.activity(names, ACT_MEMCPY_IN_FUSION_BUFFER,
                           multi) as sp:
            fused, fresh = _pack_fused(
                arrays, response, self._arena if use_arena else None)
            sp.nbytes = fused.nbytes
        (self._m_ring_ops if ring is not None
         else self._m_star_ops).inc()
        wire = response.wire_dtype
        if wire != _wd.WIRE_NONE:
            result = self._compressed_allreduce(fused, wire, ring,
                                                names)
            with self.activity(names, ACT_MEMCPY_OUT_FUSION_BUFFER,
                               multi, result.nbytes):
                _unpack_fused(entries, arrays, result, response)
            return Status.OK()
        if ring is not None:
            # allreduce is not in-place at the API: never mutate a buffer
            # that may alias the caller's tensor.
            buf = fused if (fresh and fused.flags.writeable) \
                else fused.copy()
            result = ring.allreduce_(buf)
        elif self._zero_copy:
            # Zero-copy star: peers land in scratch views / fresh
            # arrays; no byte object is ever materialized.
            if ctl.is_coordinator:
                acc = np.array(fused, dtype=dtype, copy=True)
                outs = [None] * ctl.size
                for r in range(1, ctl.size):
                    outs[r] = self._gather_arena.typed(
                        (r - 1) * fused.nbytes, dtype, fused.size)
                ctl.gather_data_into(fused, outs)
                for r in range(1, ctl.size):
                    if not _native.sum_into(acc, outs[r]):
                        acc += outs[r]
                ctl.broadcast_data(acc)
                result = acc
            else:
                ctl.gather_data_into(fused, None)
                result = np.empty(fused.size, dtype)
                ctl.broadcast_data_into(None, result)
        else:
            gathered = ctl.gather_data(fused)
            if gathered is not None:  # coordinator
                # gathered[0] is our own fused view — sum into a fresh
                # buffer so the caller's tensor is never mutated.
                acc = np.array(fused, dtype=dtype, copy=True)
                for data in gathered[1:]:
                    src = np.frombuffer(data, dtype=dtype)
                    if not _native.sum_into(acc, src):
                        acc += src
                ctl.broadcast_data(acc)
                result = acc
            else:
                result = _np_from_bytes(ctl.broadcast_data(None), dtype)

        with self.activity(names, ACT_MEMCPY_OUT_FUSION_BUFFER, multi,
                           result.nbytes):
            _unpack_fused(entries, arrays, result, response)
        return Status.OK()

    def _compressed_allreduce(self, fused: np.ndarray, wire: int,
                              ring, names) -> np.ndarray:
        """Allreduce with the negotiated wire dtype applied to every
        wire leg: compress AFTER the (prescaled) fusion pack, move and
        reduce in the wire representation, decompress ONCE into a
        fresh full-precision result the unpack may alias. The verdict
        and the route are both world-identical (broadcast response +
        negotiated sizes), so every rank takes the same branch."""
        ctl = self._ctl
        src_dtype = fused.dtype
        count = fused.size
        if ring is not None:
            wire = _wd.ring_wire(wire)
        wire_nbytes = _wd.compressed_nbytes(wire, count,
                                            src_dtype.itemsize)

        if wire == _wd.WIRE_INT8:
            # Error feedback (Deep Gradient Compression): add last
            # step's quantization residual before quantizing, keep
            # this step's error for the next one. Rank-local state by
            # design — each rank compensates its own error.
            qbuf = compress_send_payload(fused, wire, self._ef,
                                         tuple(names))
            if ctl.is_coordinator:
                if self._zero_copy:
                    outs = [None] * ctl.size
                    for r in range(1, ctl.size):
                        outs[r] = self._gather_arena.typed(
                            (r - 1) * wire_nbytes, np.uint8,
                            wire_nbytes)
                    ctl.gather_data_into(qbuf, outs)
                    peers = outs[1:]
                else:
                    peers = ctl.gather_data(qbuf)[1:]
                out_buf = _wd.reduce_wire(qbuf, peers, wire,
                                          src_dtype, count)
                ctl.broadcast_data(out_buf)
                return _wd.dequantize(out_buf, src_dtype, count)
            if self._zero_copy:
                ctl.gather_data_into(qbuf, None)
                rbuf = np.empty(wire_nbytes, np.uint8)
                ctl.broadcast_data_into(None, rbuf)
            else:
                ctl.gather_data(qbuf)
                rbuf = ctl.broadcast_data(None)
            return _wd.dequantize(rbuf, src_dtype, count)

        # Cast wires (bf16/fp16): reduction happens IN the wire dtype
        # (native hvd_sum_into converts pairwise through f32), exactly
        # like the native steady coordinator — the Python and C legs
        # are numerically interchangeable. The wire arena is safe for
        # the ring leg too: the ring mutates the WIRE buffer in place,
        # but outputs alias only the fresh decompressed result.
        np_wire = _wd.wire_np_dtype(wire)
        warr = compress_send_payload(
            fused, wire,
            out=self._wire_arena.typed(0, np_wire, count)
            if self._zero_copy else None)
        if ring is not None:
            result_wire = ring.allreduce_(warr)
            return _wd.decompress(result_wire, wire, src_dtype, count)
        if ctl.is_coordinator:
            acc = np.array(warr, copy=True)
            if self._zero_copy:
                outs = [None] * ctl.size
                for r in range(1, ctl.size):
                    outs[r] = self._gather_arena.typed(
                        (r - 1) * wire_nbytes, np_wire, count)
                ctl.gather_data_into(warr, outs)
                peers = outs[1:]
            else:
                peers = ctl.gather_data(warr)[1:]
            _wd.reduce_wire(acc, peers, wire, src_dtype, count)
            ctl.broadcast_data(acc)
            return _wd.decompress(acc, wire, src_dtype, count)
        if self._zero_copy:
            ctl.gather_data_into(warr, None)
            rarr = np.empty(count, np_wire)
            ctl.broadcast_data_into(None, rarr)
        else:
            ctl.gather_data(warr)
            rarr = ctl.broadcast_data(None)
        return _wd.decompress(rarr, wire, src_dtype, count)

    # -- allgather (multi-entry: fused responses) ------------------------
    def execute_allgather(self, entries, response: Response) -> Status:
        ctl = self._ctl
        arrays = [np.ascontiguousarray(_to_numpy(e.tensor))
                  for e in entries]
        names = [e.tensor_name for e in entries]
        multi = len(entries) > 1  # single-tensor pack is a view
        comp, rank_counts = _allgather_layout(entries, arrays, response,
                                              ctl.size)
        with self.activity(names, ACT_MEMCPY_IN_FUSION_BUFFER,
                           multi) as sp:
            packed = _pack_flat(
                arrays, self._arena if (self._zero_copy and multi)
                else None)
            sp.nbytes = packed.nbytes
        wire = response.wire_dtype
        if wire != _wd.WIRE_NONE:
            result = self._compressed_allgather(packed, wire,
                                                rank_counts)
        elif self._zero_copy:
            # Gather straight into the rank-major result: peer r's
            # block IS result[off_r : off_r + n_r], so the gathered
            # world buffer is assembled with zero intermediate copies.
            total = sum(rank_counts)
            result = np.empty(total, packed.dtype)
            offs = [0] * ctl.size
            for r in range(1, ctl.size):
                offs[r] = offs[r - 1] + rank_counts[r - 1]
            if ctl.is_coordinator:
                outs = [None] * ctl.size
                for r in range(1, ctl.size):
                    outs[r] = result[offs[r]:offs[r] + rank_counts[r]]
                ctl.gather_data_into(packed, outs)
                result[:rank_counts[0]] = packed
                ctl.broadcast_data(result)
            else:
                ctl.gather_data_into(packed, None)
                ctl.broadcast_data_into(None, result)
        else:
            gathered = ctl.gather_data(packed)
            if gathered is not None:
                _COPY_METRIC.inc()  # world-blob join (fallback tier)
                blob = b"".join(gathered)
                result = _np_from_bytes(ctl.broadcast_data(blob),
                                        packed.dtype)
            else:
                result = _np_from_bytes(ctl.broadcast_data(None),
                                        packed.dtype)
        with self.activity(names, ACT_MEMCPY_OUT_FUSION_BUFFER, multi,
                           result.nbytes):
            _unpack_allgather(entries, arrays, result, comp,
                              rank_counts)
        return Status.OK()

    def _compressed_allgather(self, packed: np.ndarray, wire: int,
                              rank_counts) -> np.ndarray:
        """Allgather with the negotiated CAST wire on the world
        exchange: every rank ships its block at wire width, the
        gathered world blob moves and broadcasts at wire width, and
        each rank decompresses ONCE into the full-dtype result the
        unpack may alias. int8 never reaches here — the coordinator's
        verdict degrades it to bf16 (wire_dtype.allgather_wire)
        because a concatenated blob cannot carry per-rank scales."""
        ctl = self._ctl
        src_dtype = packed.dtype
        np_wire = _wd.wire_np_dtype(wire)
        total = sum(rank_counts)
        warr = compress_send_payload(
            packed, wire,
            out=self._wire_arena.typed(0, np_wire, packed.size)
            if self._zero_copy else None)
        if self._zero_copy:
            wres = np.empty(total, np_wire)
            offs = [0] * ctl.size
            for r in range(1, ctl.size):
                offs[r] = offs[r - 1] + rank_counts[r - 1]
            if ctl.is_coordinator:
                # Peers land straight in their rank-major windows of
                # the wire result; nothing is ever re-assembled.
                outs = [None] * ctl.size
                for r in range(1, ctl.size):
                    outs[r] = wres[offs[r]:offs[r] + rank_counts[r]]
                ctl.gather_data_into(warr, outs)
                wres[:rank_counts[0]] = warr
                ctl.broadcast_data(wres)
            else:
                ctl.gather_data_into(warr, None)
                ctl.broadcast_data_into(None, wres)
            return _wd.decompress(wres, wire, src_dtype, total)
        gathered = ctl.gather_data(warr)
        if gathered is not None:
            wres = np.empty(total, np_wire)
            pos = 0
            for r, g in enumerate(gathered):
                n = rank_counts[r]
                src = g if isinstance(g, np.ndarray) \
                    else np.frombuffer(g, np_wire, count=n)
                wres[pos:pos + n] = src
                pos += n
            _COPY_METRIC.inc()  # store-and-forward re-assembly
            ctl.broadcast_data(wres)
            return _wd.decompress(wres, wire, src_dtype, total)
        return _wd.decompress(
            _np_from_bytes(ctl.broadcast_data(None), np_wire),
            wire, src_dtype, total)

    # -- broadcast -------------------------------------------------------
    def execute_broadcast(self, entries, response: Response) -> Status:
        ctl = self._ctl
        (entry,) = entries
        orig = _to_numpy(entry.tensor)
        # ascontiguousarray promotes 0-d to (1,); keep the true shape —
        # broadcast is the one collective defined on scalars.
        arr = np.ascontiguousarray(orig)
        if self._zero_copy:
            if ctl.rank == entry.root_rank:
                # The payload ships straight from the tensor's memory;
                # the output is one fresh copy (never an alias of the
                # user's input).
                ctl.broadcast_data(arr, root_rank=entry.root_rank)
                result = np.array(arr, copy=True)
            else:
                flat = np.empty(arr.size, arr.dtype)
                ctl.broadcast_data_into(None, flat,
                                        root_rank=entry.root_rank)
                result = flat
            entry.output = _restore(entry,
                                    result.reshape(orig.shape))
            return Status.OK()
        if ctl.rank == entry.root_rank:
            _COPY_METRIC.inc()  # send-side tobytes (fallback tier)
            data = ctl.broadcast_data(arr.tobytes(),
                                      root_rank=entry.root_rank)
        else:
            data = ctl.broadcast_data(None, root_rank=entry.root_rank)
        result = _np_from_bytes(data, arr.dtype).reshape(orig.shape)
        entry.output = _restore(entry, result)
        return Status.OK()

    # -- alltoall (TPU-native extension) ---------------------------------
    def execute_alltoall(self, entries, response: Response) -> Status:
        ctl = self._ctl
        (entry,) = entries
        arr = np.ascontiguousarray(_to_numpy(entry.tensor))
        size = ctl.size
        if self._zero_copy:
            per_rank = arr.shape[0] // size
            if ctl.is_coordinator:
                outs = [None] * size
                for r in range(1, size):
                    outs[r] = self._gather_arena.typed(
                        (r - 1) * arr.nbytes, arr.dtype, arr.size)
                ctl.gather_data_into(arr, outs)
                mats = [arr] + [outs[r].reshape(arr.shape)
                                for r in range(1, size)]
                payloads = [np.concatenate(
                    [m[d * per_rank:(d + 1) * per_rank] for m in mats])
                    for d in range(size)]
                ctl.scatter_data_into(payloads, None)
                result = payloads[0]
            else:
                ctl.gather_data_into(arr, None)
                result = np.empty(arr.size, arr.dtype)
                ctl.scatter_data_into(None, result)
            entry.output = _restore(entry, result.reshape(arr.shape))
            return Status.OK()
        _COPY_METRIC.inc()  # send-side tobytes (fallback tier)
        gathered = ctl.gather_data(arr.tobytes())
        if gathered is not None:
            mats = [np.frombuffer(g, dtype=arr.dtype).reshape(arr.shape)
                    for g in gathered]
            # destination d receives block d of every source, in rank order
            per_rank = arr.shape[0] // size
            payloads = []
            for d in range(size):
                block = np.concatenate(
                    [m[d * per_rank:(d + 1) * per_rank] for m in mats])
                _COPY_METRIC.inc()  # per-destination tobytes
                payloads.append(block.tobytes())
            data = ctl.scatter_data(payloads)
        else:
            data = ctl.scatter_data(None)
        result = _np_from_bytes(data, arr.dtype).reshape(arr.shape)
        entry.output = _restore(entry, result)
        return Status.OK()

    # -- reducescatter (TPU-native extension) ----------------------------
    def execute_reducescatter(self, entries, response: Response) -> Status:
        ctl = self._ctl
        (entry,) = entries
        arr = np.ascontiguousarray(_to_numpy(entry.tensor))
        fresh = False
        if response.prescale_factor != 1.0:
            arr = arr * np.asarray(response.prescale_factor, arr.dtype)
            fresh = True
        size = ctl.size
        per_rank = arr.shape[0] // size
        row = int(np.prod(arr.shape[1:], dtype=np.int64)) \
            if arr.ndim > 1 else 1
        per_elems = per_rank * row
        # Routing by UNCOMPRESSED bytes, like allreduce — the wire
        # dtype must not flip the route.
        wire = response.wire_dtype
        ring = self._ring_for(arr.nbytes) \
            if arr.shape[0] % size == 0 else None
        if ring is not None:
            if wire != _wd.WIRE_NONE:
                # Ring legs sum link-by-link, so int8 degrades to bf16
                # (ring_wire) and the reduction happens IN the wire
                # dtype — the compressed-allreduce ring discipline.
                rw = _wd.ring_wire(wire)
                wbuf = compress_send_payload(arr.reshape(-1), rw)
                result = _wd.decompress(
                    ring.reduce_scatter_(wbuf), rw, arr.dtype,
                    per_elems).reshape((per_rank,) + arr.shape[1:])
            else:
                flat = arr.reshape(-1)
                buf = flat if (fresh and flat.flags.writeable) \
                    else flat.copy()
                result = ring.reduce_scatter_(buf).reshape(
                    (per_rank,) + arr.shape[1:])
            if response.postscale_factor != 1.0:
                result = result * np.asarray(response.postscale_factor,
                                             arr.dtype)
            entry.output = _restore(entry, result)
            return Status.OK()
        if wire != _wd.WIRE_NONE:
            result = self._compressed_reducescatter(
                arr, wire, per_elems).reshape(
                (per_rank,) + arr.shape[1:])
            if response.postscale_factor != 1.0:
                result = result * np.asarray(response.postscale_factor,
                                             arr.dtype)
            entry.output = _restore(entry, result)
            return Status.OK()
        if self._zero_copy:
            if ctl.is_coordinator:
                outs = [None] * size
                for r in range(1, size):
                    outs[r] = self._gather_arena.typed(
                        (r - 1) * arr.nbytes, arr.dtype, arr.size)
                ctl.gather_data_into(arr, outs)
                acc = arr.reshape(-1).copy()
                for r in range(1, size):
                    if not _native.sum_into(acc, outs[r]):
                        acc += outs[r]
                acc = acc.reshape(arr.shape)
                ctl.scatter_data_into(
                    [acc[d * per_rank:(d + 1) * per_rank]
                     for d in range(size)], None)
                # acc is fresh: this rank's slice may back the output
                result = acc[:per_rank]
            else:
                ctl.gather_data_into(arr, None)
                flat = np.empty(per_rank * row, arr.dtype)
                ctl.scatter_data_into(None, flat)
                result = flat.reshape((per_rank,) + arr.shape[1:])
            if response.postscale_factor != 1.0:
                result = result * np.asarray(response.postscale_factor,
                                             arr.dtype)
            entry.output = _restore(
                entry, result.reshape((per_rank,) + arr.shape[1:]))
            return Status.OK()

    def _compressed_reducescatter(self, arr: np.ndarray, wire: int,
                                  per_elems: int) -> np.ndarray:
        """Reducescatter star with the negotiated wire dtype on every
        leg, returning this rank's FLAT full-dtype slice (fresh —
        postscale/outputs may alias it). Cast wires accumulate IN the
        wire dtype, exactly like _compressed_allreduce. int8 keeps
        full aggressiveness here — unlike a ring link, the star's
        coordinator can dequantize each rank's payload with ITS OWN
        scale into a full-precision accumulator and requantize each
        OUTPUT slice with a fresh scale, so per-rank scales never
        mix. No error feedback: the output is a world-reduced slice,
        not this rank's next-step gradient, so there is no residual
        chain to compensate."""
        ctl = self._ctl
        size = ctl.size
        src_dtype = arr.dtype
        flat = arr.reshape(-1)
        count = flat.size
        wire_nbytes = _wd.compressed_nbytes(wire, count,
                                            src_dtype.itemsize)
        slice_nbytes = _wd.compressed_nbytes(wire, per_elems,
                                             src_dtype.itemsize)

        if wire == _wd.WIRE_INT8:
            qbuf = compress_send_payload(flat, wire)
            if ctl.is_coordinator:
                if self._zero_copy:
                    outs = [None] * size
                    for r in range(1, size):
                        outs[r] = self._gather_arena.typed(
                            (r - 1) * wire_nbytes, np.uint8,
                            wire_nbytes)
                    ctl.gather_data_into(qbuf, outs)
                    peers = outs[1:]
                else:
                    peers = ctl.gather_data(qbuf)[1:]
                acc = _wd.dequantize(qbuf, src_dtype, count)
                for p in peers:
                    acc += _wd.dequantize(p, src_dtype, count)
                # Every slice — the coordinator's own included — rides
                # through the codec, so all ranks' outputs carry the
                # same quantization treatment.
                payloads = [
                    _wd.quantize(acc[d * per_elems:(d + 1) * per_elems])
                    for d in range(size)]
                if self._zero_copy:
                    ctl.scatter_data_into(payloads, None)
                    rbuf = payloads[0]
                else:
                    rbuf = ctl.scatter_data(payloads)
                return _wd.dequantize(rbuf, src_dtype, per_elems)
            if self._zero_copy:
                ctl.gather_data_into(qbuf, None)
                rbuf = np.empty(slice_nbytes, np.uint8)
                ctl.scatter_data_into(None, rbuf)
            else:
                ctl.gather_data(qbuf)
                rbuf = ctl.scatter_data(None)
            return _wd.dequantize(rbuf, src_dtype, per_elems)

        np_wire = _wd.wire_np_dtype(wire)
        warr = compress_send_payload(
            flat, wire,
            out=self._wire_arena.typed(0, np_wire, count)
            if self._zero_copy else None)
        if ctl.is_coordinator:
            acc = np.array(warr, copy=True)
            if self._zero_copy:
                outs = [None] * size
                for r in range(1, size):
                    outs[r] = self._gather_arena.typed(
                        (r - 1) * wire_nbytes, np_wire, count)
                ctl.gather_data_into(warr, outs)
                peers = outs[1:]
            else:
                peers = ctl.gather_data(warr)[1:]
            _wd.reduce_wire(acc, peers, wire, src_dtype, count)
            slices = [acc[d * per_elems:(d + 1) * per_elems]
                      for d in range(size)]
            if self._zero_copy:
                ctl.scatter_data_into(slices, None)
            else:
                ctl.scatter_data(slices)
            return _wd.decompress(slices[0], wire, src_dtype,
                                  per_elems)
        if self._zero_copy:
            ctl.gather_data_into(warr, None)
            wsl = np.empty(per_elems, np_wire)
            ctl.scatter_data_into(None, wsl)
        else:
            ctl.gather_data(warr)
            wsl = ctl.scatter_data(None)
        return _wd.decompress(wsl, wire, src_dtype, per_elems)
        _COPY_METRIC.inc()  # send-side tobytes (fallback tier)
        gathered = ctl.gather_data(arr.tobytes())
        if gathered is not None:
            _COPY_METRIC.inc()  # writable accumulator materialization
            acc = np.frombuffer(bytearray(gathered[0]), dtype=arr.dtype)
            for data in gathered[1:]:
                src = np.frombuffer(data, dtype=arr.dtype)
                if not _native.sum_into(acc, src):
                    acc += src
            acc = acc.reshape(arr.shape)
            _COPY_METRIC.inc(size)  # per-slice tobytes
            payloads = [acc[d * per_rank:(d + 1) * per_rank].tobytes()
                        for d in range(size)]
            data = ctl.scatter_data(payloads)
        else:
            data = ctl.scatter_data(None)
        result = _np_from_bytes(data, arr.dtype).reshape(
            (per_rank,) + arr.shape[1:])
        if response.postscale_factor != 1.0:
            result = result * np.asarray(response.postscale_factor,
                                         arr.dtype)
        entry.output = _restore(entry, result)
        return Status.OK()

    def execute_barrier(self, entries, response: Response) -> Status:
        gathered = self._ctl.gather_data(b"")
        if gathered is not None:
            self._ctl.broadcast_data(b"")
        else:
            self._ctl.broadcast_data(None)
        return Status.OK()
