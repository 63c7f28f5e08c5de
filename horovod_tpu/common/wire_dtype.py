"""Wire-dtype gradient compression: the on-the-wire codec.

Upstream Horovod's ``Compression`` API casts gradients to fp16 at the
FRAMEWORK layer (Sergeev & Del Balso 2018) — every byte the data plane
moves is already half-width by the time the runtime sees it. This
module is the TPU-native deepening of that idea: the wire dtype is a
**per-request negotiated attribute**, resolved by the coordinator to
one world-coherent choice per fused batch (the common denominator of
every rank's proposal) and broadcast in the Response, and the op
backends compress into the fusion arenas / decompress on recv-into so
only WIRE bytes shrink — user tensors, accumulators and outputs keep
their full dtype. ``int8`` adds per-tensor error-feedback residuals
(Deep Gradient Compression, Lin et al. 2018): the quantization error
of step k is added back into step k+1's payload, so the time-averaged
update is unbiased.

This module is also THE shared dtype table (satellite of ISSUE 9): the
framework-level ``common/compression.py`` helper and this wire codec
both answer "is this tensor a float?" through :func:`is_floating_name`
— the previous string matching in two places is exactly how jax/
ml_dtypes ``bfloat16`` fell through one of them.

Code families (one byte each on the wire; hvdlint's wire-protocol
analyzer enforces pairwise distinctness per family):

* ``WIRE_*`` — the negotiated wire dtype of a payload.  Ordered by
  aggressiveness: the coordinator resolves a fused batch to the MIN
  over ranks, so one rank proposing ``none`` degrades the whole batch
  to uncompressed (heterogeneous knobs negotiate, never crash).
* ``ALG_*`` — the collective algorithm the coordinator stamps on a
  fused Response: ``DEFAULT`` keeps each backend's own routing
  heuristics (byte-identical to pre-compression behavior), ``STAR``/
  ``RING`` force the flat socket paths, ``TWOLEVEL`` selects the
  hierarchical intra-host-reduce / cross-host-ring / intra-host-
  broadcast plane (ops/shm_ops.py).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from horovod_tpu.common.message import DataType

# -- wire dtype codes (u8 on the wire; min-resolved across ranks) ------
WIRE_NONE = 0
WIRE_BF16 = 1
WIRE_FP16 = 2
WIRE_INT8 = 3

# -- algorithm codes (u8 on the wire; stamped by the coordinator) ------
ALG_DEFAULT = 0
ALG_STAR = 1
ALG_RING = 2
ALG_TWOLEVEL = 3

WIRE_NAMES = {WIRE_NONE: "none", WIRE_BF16: "bf16",
              WIRE_FP16: "fp16", WIRE_INT8: "int8"}
_NAME_WIRES = {v: k for k, v in WIRE_NAMES.items()}
ALG_NAMES = {ALG_DEFAULT: "default", ALG_STAR: "star",
             ALG_RING: "ring", ALG_TWOLEVEL: "twolevel"}

# Request dtypes a wire cast can shrink. fp16/bf16 tensors are already
# half-width and int tensors have no meaningful reduced-precision sum.
COMPRESSIBLE = frozenset((DataType.FLOAT32, DataType.FLOAT64))

# THE shared float-dtype table (see module docstring): numpy builtin
# names plus the ml_dtypes extension names jax surfaces on host
# buffers. Both common/compression.py and this codec consult it.
FLOATING_DTYPE_NAMES = frozenset((
    "float16", "float32", "float64", "bfloat16",
    "float8_e4m3fn", "float8_e5m2",
))

# int8 wire layout: one f32 scale, then count int8 lanes. The scale
# rides inside the payload (not the control frame) so every data plane
# that can move bytes can move quantized tensors unchanged.
_INT8_HDR = 4


def is_floating_name(name: str) -> bool:
    return name in FLOATING_DTYPE_NAMES


def is_floating(dtype_like) -> bool:
    """Shared float probe for numpy/jax/ml_dtypes dtypes — name-keyed
    via one table instead of per-call string lists. jax array dtypes
    (including ``jax.numpy.bfloat16``) expose ``.name``; anything else
    normalizes through ``np.dtype``."""
    name = getattr(dtype_like, "name", None)
    if name is None:
        name = np.dtype(dtype_like).name
    return name in FLOATING_DTYPE_NAMES


def wire_code_of(name: str) -> int:
    """Knob string -> WIRE_* code; raises on a typo (a silently-picked
    default would diverge ranks' proposals without anyone noticing)."""
    code = _NAME_WIRES.get(name.strip().lower())
    if code is None:
        raise ValueError(
            f"HOROVOD_COMPRESSION={name!r}: must be one of "
            f"{sorted(_NAME_WIRES)}")
    return code


def ring_wire(wire: int) -> int:
    """The wire dtype a RING leg actually carries: per-rank int8
    scales cannot sum link-by-link, so int8 degrades to bf16 — ONE
    rule shared by every plane that routes onto a ring (the route and
    the verdict are both world-identical, so the degrade is too)."""
    return WIRE_BF16 if wire == WIRE_INT8 else wire


def allgather_wire(wire: int) -> int:
    """The wire dtype an ALLGATHER verdict can carry: the gathered
    world blob is ONE payload whose blocks concatenate byte-for-byte,
    and per-rank int8 scale headers cannot ride inside a single
    contiguous buffer, so int8 degrades to bf16 (the cast wires
    concatenate losslessly). Stamped by the coordinator, so the
    degrade is world-identical like :func:`ring_wire`'s."""
    return WIRE_BF16 if wire == WIRE_INT8 else wire


def resolve(codes) -> int:
    """The world's common denominator for one tensor's proposals: the
    LEAST aggressive request wins, so a single rank launched with
    compression off degrades the batch to a dtype every rank can
    speak. (Knob heterogeneity only — every rank must run the same
    wire layout, since the proposal byte rides the control frames.)"""
    out = None
    for c in codes:
        out = c if out is None else min(out, c)
    return WIRE_NONE if out is None else out


def _np_bf16():
    import ml_dtypes
    return np.dtype(ml_dtypes.bfloat16)


def wire_np_dtype(wire: int):
    """numpy dtype of the wire lanes for the CAST wires; int8 payloads
    are raw uint8 (scale header + lanes) and have no single lane
    dtype."""
    if wire == WIRE_BF16:
        return _np_bf16()
    if wire == WIRE_FP16:
        return np.dtype(np.float16)
    raise ValueError(f"wire dtype {wire} has no lane dtype")


def wire_datatype(wire: int) -> DataType:
    """DataType a compressed spec-frame segment declares on the wire
    (cast wires only — int8 never rides the speculative fused cycle)."""
    if wire == WIRE_BF16:
        return DataType.BFLOAT16
    if wire == WIRE_FP16:
        return DataType.FLOAT16
    raise ValueError(f"wire dtype {wire} has no DataType")


def compressed_nbytes(wire: int, count: int, src_itemsize: int) -> int:
    """Payload bytes ``count`` elements occupy at ``wire``."""
    if wire == WIRE_NONE:
        return count * src_itemsize
    if wire in (WIRE_BF16, WIRE_FP16):
        return count * 2
    if wire == WIRE_INT8:
        return _INT8_HDR + count
    raise ValueError(f"unknown wire dtype {wire}")


def compress(arr: np.ndarray, wire: int,
             out: Optional[np.ndarray] = None) -> np.ndarray:
    """Flat contiguous src array -> wire representation. ``out`` (a
    preallocated wire-dtype view, e.g. a fusion-arena region) makes
    the cast allocation-free on the steady path; int8 callers go
    through :func:`quantize` instead (the scale must be computed)."""
    if wire == WIRE_NONE:
        return arr
    if wire == WIRE_INT8:
        return quantize(arr)
    np_wire = wire_np_dtype(wire)
    if out is None:
        return arr.astype(np_wire)
    cast_into(arr, out)
    return out


def cast_into(src: np.ndarray, dst: np.ndarray) -> None:
    """dst[:] = src with a dtype cast — the native ``hvd_cast`` kernel
    when it speaks both dtypes (f32<->bf16/f16), numpy's casting
    machinery otherwise. Never allocates a payload-sized temporary on
    the native path."""
    from horovod_tpu import native as _native
    if not _native.cast_into(src, dst):
        # ml_dtypes registers numpy casts, so copyto handles the
        # bf16 directions too; 'unsafe' covers f64 sources.
        np.copyto(dst, src, casting="unsafe")


def decompress(buf, wire: int, src_np_dtype, count: int) -> np.ndarray:
    """Wire bytes/array -> a FRESH array of the tensor's real dtype
    (fresh on purpose: decompressed results back user-visible outputs,
    which must never alias wire/arena memory)."""
    src_np_dtype = np.dtype(src_np_dtype)
    if wire == WIRE_NONE:
        a = buf if isinstance(buf, np.ndarray) \
            else np.frombuffer(buf, dtype=src_np_dtype)
        return np.array(a, dtype=src_np_dtype, copy=True)
    if wire == WIRE_INT8:
        return dequantize(buf, src_np_dtype, count)
    np_wire = wire_np_dtype(wire)
    w = buf if isinstance(buf, np.ndarray) and buf.dtype == np_wire \
        else np.frombuffer(buf, dtype=np_wire, count=count)
    out = np.empty(count, src_np_dtype)
    cast_into(w, out)
    return out


# -- int8 with error feedback ------------------------------------------

# Fallback-copy observability hook (hvd_data_copies_total — the SAME
# counter as socket_ops/runtime by registry name-memoization, attached
# by SocketBackend.attach_metrics). The numpy codec legs materialize
# payload-sized temporaries the native codec (hvd_quant8/hvd_dequant8)
# deletes; ticking them per leg keeps "is the zero-copy plane
# engaged" an honest single metrics read. None (unattached) records
# nothing.
_COPY_METRIC = None


def attach_copy_counter(metric) -> None:
    global _COPY_METRIC
    _COPY_METRIC = metric


def _count_copy() -> None:
    m = _COPY_METRIC
    if m is not None:
        m.inc()


def _quantize_numpy(arr: np.ndarray, buf: np.ndarray) -> None:
    """The numpy codec leg (bit-identical reference of hvd_quant8's
    plain mode): payload-sized temporaries and all — counted as ONE
    fallback copy."""
    n = arr.size
    scale = float(np.max(np.abs(arr))) / 127.0 if n else 0.0
    if scale == 0.0:
        scale = 1.0
    buf[:_INT8_HDR].view(np.float32)[0] = scale
    q = buf[_INT8_HDR:].view(np.int8)
    # two-step on purpose: rint in float, clip, then narrow — a direct
    # int8 cast of an out-of-range float is undefined in numpy
    tmp = np.rint(arr * (arr.dtype.type(1.0 / scale)))
    np.clip(tmp, -127, 127, out=tmp)
    q[:] = tmp.astype(np.int8)
    _count_copy()


def quantize(arr: np.ndarray) -> np.ndarray:
    """f32/f64 -> [f32 scale | int8 lanes] as one uint8 buffer. Scale
    is max|x|/127 (1.0 for an all-zero tensor so dequantize is exact);
    lanes round to nearest-even. One native pass (hvd_quant8) when the
    core speaks the dtype — scale scan, scaled round and saturate
    without a single payload temporary, bit-identical to the numpy
    leg."""
    from horovod_tpu import native as _native
    buf = np.empty(_INT8_HDR + arr.size, np.uint8)
    if not _native.quant8(arr, buf):
        _quantize_numpy(np.ascontiguousarray(arr), buf)
    return buf


def quantize_ef(arr: np.ndarray, ef: "ErrorFeedback",
                key: tuple) -> np.ndarray:
    """int8 quantize with FUSED error feedback: compensate
    (arr + residual), scan, quantize and store the next-step residual
    in one native pass (hvd_quant8 with residual buffers) instead of
    the apply -> quantize -> update triple and its three payload
    temporaries. Bit-identical to the classic triple — the fallback
    IS the classic triple."""
    from horovod_tpu import native as _native
    res_in = ef.residual(key, arr)
    res_out = ef.residual_buffer(key, arr)
    buf = np.empty(_INT8_HDR + arr.size, np.uint8)
    if _native.quant8(arr, buf, residual=res_in,
                      residual_out=res_out):
        ef.put(key, res_out)
        return buf
    comp = ef.apply(key, arr)
    _quantize_numpy(comp, buf)
    ef.update(key, comp, buf)
    return buf


def dequantize(buf, src_np_dtype, count: int) -> np.ndarray:
    """[scale|int8] buffer -> fresh src-dtype array. Native single
    pass (hvd_dequant8) when available; the numpy leg round-trips a
    payload-sized astype temporary (counted)."""
    from horovod_tpu import native as _native
    src_np_dtype = np.dtype(src_np_dtype)
    raw = np.frombuffer(buf, np.uint8, count=_INT8_HDR + count)
    out = np.empty(count, src_np_dtype)
    if _native.dequant8(raw, out):
        return out
    scale = float(raw[:_INT8_HDR].view(np.float32)[0])
    q = raw[_INT8_HDR:].view(np.int8)
    np.multiply(q.astype(src_np_dtype),
                np.asarray(scale, src_np_dtype), out=out)
    _count_copy()
    return out


class ErrorFeedback:
    """Per-tensor-batch int8 residual store (rank-LOCAL by design —
    each rank compensates its OWN quantization error, so residuals
    legitimately differ across ranks and are deliberately NOT
    world-replicated state). Keyed by the fused batch's name tuple:
    steady training loops repeat the same batches, and a membership
    change simply starts a fresh residual. LRU-capped: past _CAP
    keys the OLDEST residual is dropped — never the whole store, or
    a workload with more distinct batches than the cap would lose
    every compensation chain on every step."""

    _CAP = 64

    def __init__(self):
        from collections import OrderedDict
        self._residuals: "OrderedDict[tuple, np.ndarray]" = \
            OrderedDict()

    def apply(self, key: tuple, arr: np.ndarray) -> np.ndarray:
        """arr + residual(key) as a FRESH array (never mutates arr —
        it may alias a caller tensor or arena memory)."""
        r = self._residuals.get(key)
        if r is None or r.size != arr.size:
            return np.array(arr, copy=True)
        return arr + r.astype(arr.dtype, copy=False)

    def update(self, key: tuple, compensated: np.ndarray,
               qbuf: np.ndarray) -> None:
        """residual = compensated - dequant(sent): what the wire lost
        this step rides into the next one."""
        if key not in self._residuals \
                and len(self._residuals) >= self._CAP:
            self._residuals.popitem(last=False)
        sent = dequantize(qbuf, compensated.dtype, compensated.size)
        self._residuals[key] = compensated - sent
        self._residuals.move_to_end(key)

    # -- fused native entry (quantize_ef / hvd_quant8) -----------------
    def residual(self, key: tuple, arr: np.ndarray):
        """The stored residual when it can feed the native fused pass
        directly (same lane count AND dtype — a mismatch starts a
        fresh compensation chain, exactly like apply's size check)."""
        r = self._residuals.get(key)
        if r is None or r.size != arr.size or r.dtype != arr.dtype:
            return None
        return r

    def residual_buffer(self, key: tuple, arr: np.ndarray) -> np.ndarray:
        """Destination for the fused pass's next-step residual. The
        stored residual itself when it matches — hvd_quant8 reads lane
        i before overwriting it, so aliasing residual/residual_out is
        safe and saves the allocation — else a fresh buffer."""
        r = self._residuals.get(key)
        if r is not None and r.size == arr.size \
                and r.dtype == arr.dtype:
            return r
        return np.empty(arr.size, arr.dtype)

    def put(self, key: tuple, residual: np.ndarray) -> None:
        """Store a residual computed by the fused native pass (the
        update() twin without the dequantize round-trip)."""
        if key not in self._residuals \
                and len(self._residuals) >= self._CAP:
            self._residuals.popitem(last=False)
        self._residuals[key] = residual
        self._residuals.move_to_end(key)

    def drop(self, key: tuple) -> None:
        self._residuals.pop(key, None)


def reduce_wire(own: np.ndarray, peers: List, wire: int,
                src_np_dtype, count: int) -> np.ndarray:
    """Coordinator-side reduction of compressed contributions, rank
    order (own first). Cast wires sum IN the wire dtype — exactly what
    the native steady coordinator does via ``hvd_sum_into``, so the
    Python and C legs are numerically interchangeable. int8 payloads
    carry per-rank scales, so the coordinator dequantizes each into a
    full-precision accumulator and requantizes the world sum with a
    fresh scale for the broadcast. Returns the wire buffer to
    broadcast (``own`` is consumed as the accumulator for cast
    wires — callers pass a fresh array)."""
    from horovod_tpu import native as _native
    if wire in (WIRE_BF16, WIRE_FP16):
        np_wire = wire_np_dtype(wire)
        acc = own
        for p in peers:
            src = p if isinstance(p, np.ndarray) and p.dtype == np_wire \
                else np.frombuffer(p, dtype=np_wire, count=count)
            if not _native.sum_into(acc, src):
                acc += src
        return acc
    assert wire == WIRE_INT8
    accf = dequantize(own, src_np_dtype, count)
    for p in peers:
        accf += dequantize(p, src_np_dtype, count)
    return quantize(accf)


class StaticWirePolicy:
    """The non-autotuned (algorithm, wire-dtype cap) policy the
    coordinator stamps fused allreduce batches with: two-level for
    multi-host batches at/above the threshold when HOROVOD_TWO_LEVEL
    is set, each backend's own routing otherwise; never caps the
    negotiated wire dtype (the request proposals already carry the
    operator's choice). Two-level additionally requires the shm plane
    (its intra-host legs live there) — a stamp whose plane cannot
    engage would silently no-op as default routing. The autotuned
    twin is ParameterManager.plan (common/parameter_manager.py)."""

    def __init__(self, two_level: bool, threshold_bytes: int,
                 multi_host: bool, shm_enabled: bool = True):
        self._two_level = bool(two_level) and multi_host and shm_enabled
        self._threshold = max(0, int(threshold_bytes))

    def plan(self, nbytes: int):
        """-> (ALG_* code, wire cap or None)."""
        if self._two_level and nbytes >= self._threshold:
            return ALG_TWOLEVEL, None
        return ALG_DEFAULT, None


# -- process-wide "wire compression is active" latch -------------------
# Set by basics.init from Config.compression; consulted by the
# framework-level Compression helpers so a job that enables wire
# compression does not ALSO cast at the framework layer (double
# compression would quantize twice and decompress once).

_ACTIVE = WIRE_NONE


def set_active(code: int) -> None:
    global _ACTIVE
    _ACTIVE = code


def active() -> int:
    return _ACTIVE
