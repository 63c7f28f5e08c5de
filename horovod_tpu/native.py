"""ctypes loader for the native runtime core (native/libhvdtpu.so).

Role-equivalent of the reference's ``HorovodBasics`` shared-library
loading (reference: horovod/common/__init__.py:51-63 ctypes CDLL with
RTLD_GLOBAL), with one twist: if the library has not been built yet and
a compiler is available, it is built on first import (the reference
front-loads this into its 1,012-line setup.py; we have one make rule).

Set ``HOROVOD_NATIVE=0`` to force the pure-Python paths.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

from horovod_tpu.common import config as hconfig
from horovod_tpu.common import lockdep
from horovod_tpu.common import logging as hlog

_lock = lockdep.lock("native._lock")
_lib = None
_tried = False

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libhvdtpu.so")
_STAMP_PATH = _SO_PATH + ".srcsha256"
_SOURCES = ("hvdtpu.cc", "hvdtpu.h", "hvdtpu.lds", "Makefile")

# Idle-slice callback type for hvd_steady_coord (the coordinator's
# PING fan-out re-enters Python once per idle poll slice). Module
# level so _configure and callers share one ctypes identity — a
# per-call CFUNCTYPE would defeat argtype checking AND risk the
# callback being garbage-collected mid-call.
ON_IDLE_FUNC = ctypes.CFUNCTYPE(None)

# The null idle callback, shared: callers that run a steady cycle
# WITHOUT a liveness deadline previously constructed a fresh
# ON_IDLE_FUNC(0) per cycle — a per-step allocation on the hot path
# whose mid-call garbage collection the type comment above warns
# about. One module-level instance removes both hazards and survives
# elastic re-inits (common/elastic.py) unchanged.
NULL_ON_IDLE = ON_IDLE_FUNC(0)


def disabled_via_env() -> bool:
    """The one definition of 'native core disabled by the operator'.
    Two spellings for compatibility: HOROVOD_NATIVE (docs) and
    HOROVOD_TPU_NATIVE (Config.native_core, common/config.py). Exact
    legacy truthiness on purpose (only these values disable) —
    env_bool's narrower truthy set would silently drop the C++ core
    for e.g. HOROVOD_NATIVE=ON deployments. Shared by get() and the
    CI gate (tests/conftest.py), so the two can never drift."""
    return (hconfig.env_str("HOROVOD_NATIVE", "1") == "0"
            or hconfig.env_str("HOROVOD_TPU_NATIVE", "1")
            in ("0", "false"))


def _source_digest() -> str:
    """sha256 over everything the Makefile compiles the library from."""
    import hashlib
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _so_fresh() -> bool:
    """The built library exists and was built from the sources on disk.
    Decided by the source digest the build stamped beside it, never by
    mtimes: a copied or freshly checked-out tree carries mtimes that
    say nothing about which source a found library came from."""
    try:
        with open(_STAMP_PATH) as f:
            stamp = f.read().strip()
        return os.path.exists(_SO_PATH) and stamp == _source_digest()
    except OSError:
        return False


def _build(force: bool = False) -> bool:
    if not os.path.isdir(_NATIVE_DIR):
        return False
    # Multiple local ranks may race the first build. Serialize with an
    # flock'd lockfile and have make produce the .so atomically enough
    # (each rank re-checks FRESHNESS under the lock before building —
    # a bare existence check here used to defeat the stale-rebuild
    # path in get(): a source newer than the .so was never recompiled,
    # so new native entry points silently stayed missing).
    lock_path = os.path.join(_NATIVE_DIR, ".build.lock")
    try:
        import fcntl
        with open(lock_path, "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            if force:
                # A failed forced build must not leave the old library
                # looking fresh.
                if os.path.exists(_STAMP_PATH):
                    os.remove(_STAMP_PATH)
            elif _so_fresh():
                return True
            digest = _source_digest()
            tmp_target = f"libhvdtpu.build{os.getpid()}.so"
            subprocess.run(
                ["make", "-C", _NATIVE_DIR, "-s",
                 f"TARGET={tmp_target}"],
                check=True, capture_output=True, timeout=120)
            os.replace(os.path.join(_NATIVE_DIR, tmp_target), _SO_PATH)
            with open(_STAMP_PATH, "w") as f:
                f.write(digest + "\n")
            return True
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as e:
        # Loud only where a compiler exists: without one the Python
        # paths are the supported configuration.
        log = hlog.warning if compiler_available() else hlog.debug
        stderr = getattr(e, "stderr", None) or b""
        log(f"native build failed: {e}\n{stderr.decode(errors='replace')}")
        return False


def _configure(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.hvd_gather_frames.restype = ctypes.c_int
    lib.hvd_gather_frames.argtypes = [
        ctypes.POINTER(ctypes.c_int), ctypes.c_int, u8p, ctypes.c_int,
        ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_int64), u8p,
        ctypes.c_int, ctypes.POINTER(ctypes.c_double)]
    lib.hvd_broadcast_frame.restype = ctypes.c_int
    lib.hvd_broadcast_frame.argtypes = [
        ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_uint8,
        u8p, ctypes.c_int64, u8p, ctypes.c_int]
    lib.hvd_scatter_frames.restype = ctypes.c_int
    lib.hvd_scatter_frames.argtypes = [
        ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_uint8,
        ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_int64), u8p,
        ctypes.c_int]
    lib.hvd_free.restype = None
    lib.hvd_free.argtypes = [u8p]
    lib.hvd_pack.restype = None
    lib.hvd_pack.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.c_void_p]
    lib.hvd_unpack.restype = None
    lib.hvd_unpack.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p)]
    lib.hvd_sum_into.restype = ctypes.c_int
    lib.hvd_sum_into.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int]
    lib.hvd_cast.restype = ctypes.c_int
    lib.hvd_cast.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_int, ctypes.c_int]
    lib.hvd_hmac_sha256.restype = None
    lib.hvd_hmac_sha256.argtypes = [
        u8p, ctypes.c_int, ctypes.c_uint8, u8p, ctypes.c_int64, u8p]
    i64p = ctypes.POINTER(ctypes.c_int64)
    vpp = ctypes.POINTER(ctypes.c_void_p)
    u8pp = ctypes.POINTER(u8p)
    lib.hvd_sendv.restype = ctypes.c_int
    lib.hvd_sendv.argtypes = [
        ctypes.c_int, ctypes.c_uint8, vpp, i64p, ctypes.c_int,
        u8p, ctypes.c_int]
    lib.hvd_recv_into.restype = ctypes.c_int
    lib.hvd_recv_into.argtypes = [
        ctypes.c_int, u8p, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_int64,
        u8p, ctypes.c_int,
        i64p, u8p,
        ctypes.c_int, ctypes.c_int,
        u8pp]
    lib.hvd_steady_worker.restype = ctypes.c_int
    lib.hvd_steady_worker.argtypes = [
        ctypes.c_int, ctypes.c_uint8, ctypes.c_uint8,
        u8p, ctypes.c_int64,
        u8pp, i64p,
        vpp, vpp,
        i64p, ctypes.c_int,
        u8p, ctypes.c_int,
        u8p, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
        u8pp, i64p, u8p]
    lib.hvd_steady_worker_chunked.restype = ctypes.c_int
    lib.hvd_steady_worker_chunked.argtypes = [
        ctypes.c_int, ctypes.c_uint8, ctypes.c_uint8,
        u8p, ctypes.c_int64,
        u8pp, i64p,
        vpp, vpp,
        ctypes.POINTER(ctypes.c_int), ctypes.c_int64,
        vpp,
        i64p, ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        u8p, ctypes.c_int,
        u8p, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
        u8pp, i64p, u8p]
    lib.hvd_steady_coord.restype = ctypes.c_int
    lib.hvd_steady_coord.argtypes = [
        ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        ctypes.c_uint8, ctypes.c_uint8,
        u8p, ctypes.c_int64,
        u8pp, i64p,
        i64p, ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        u8pp, vpp,
        u8p, ctypes.c_int,
        u8p, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
        ON_IDLE_FUNC,
        u8p, ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_int), u8pp, i64p, u8p]
    lib.hvd_gather_frames_batched.restype = ctypes.c_int
    lib.hvd_gather_frames_batched.argtypes = [
        ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        u8p, ctypes.c_int,
        ctypes.c_uint8, vpp,
        i64p, i64p,
        u8p, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
        ON_IDLE_FUNC,
        u8p, ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), u8pp, i64p, u8p]
    lib.hvd_sendv_zc.restype = ctypes.c_int
    lib.hvd_sendv_zc.argtypes = [
        ctypes.c_int, ctypes.c_uint8, vpp, i64p, ctypes.c_int,
        u8p, ctypes.c_int,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
    lib.hvd_relay_frame.restype = ctypes.c_int
    lib.hvd_relay_frame.argtypes = [
        ctypes.c_int, ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        ctypes.c_uint8, ctypes.c_void_p, ctypes.c_int64,
        u8p, ctypes.c_int,
        u8p, ctypes.c_int,
        ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        i64p, u8p, u8pp]
    lib.hvd_build_flags.restype = ctypes.c_int
    lib.hvd_build_flags.argtypes = []
    lib.hvd_quant8.restype = ctypes.c_int
    lib.hvd_quant8.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_void_p, u8p]
    lib.hvd_dequant8.restype = ctypes.c_int
    lib.hvd_dequant8.argtypes = [
        u8p, ctypes.c_int64, ctypes.c_int, ctypes.c_void_p]


def get() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None (pure-Python fallback)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if disabled_via_env():
            return None
        if not _so_fresh() and not _build():
            # A library that was not built from the sources on disk is
            # never loaded: its ABI is unknown.
            hlog.debug("native core unavailable; using Python paths")
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
            _configure(lib)
            _lib = lib
            hlog.debug(f"native core loaded from {_SO_PATH}")
        except OSError as e:
            hlog.warning(f"failed to load native core: {e}")
    return _lib


# -- numpy-facing wrappers ----------------------------------------------

_DTYPE_CODES = {"float32": 0, "float64": 1, "int32": 2, "int64": 3,
                "uint8": 4, "float16": 5, "bfloat16": 6}


def pack(arrays):
    """Concatenate same-dtype C-contiguous flat arrays into one fresh
    buffer with a single native call (the reference's fusion-buffer
    MemcpyInFusionBuffer, collective_operations.cc:35-63). Returns
    None when the native path is unavailable (caller falls back to
    numpy concatenation)."""
    lib = get()
    if lib is None or not arrays:
        return None
    import numpy as np
    dtype = arrays[0].dtype
    for a in arrays:
        if a.dtype != dtype or not a.flags["C_CONTIGUOUS"]:
            return None
    n = len(arrays)
    srcs = (ctypes.c_void_p * n)(*[a.ctypes.data for a in arrays])
    sizes = (ctypes.c_int64 * n)(*[a.nbytes for a in arrays])
    total = sum(a.size for a in arrays)
    out = np.empty(total, dtype)
    lib.hvd_pack(srcs, sizes, n, out.ctypes.data_as(ctypes.c_void_p))
    return out


def pack_into(arrays, out) -> bool:
    """Concatenate same-dtype C-contiguous flat arrays into ``out``
    (a preallocated writable array/view of exactly the packed size)
    with ONE native call — the zero-allocation fusion-arena pack of
    the steady data plane. Returns False when the native path cannot
    serve this batch (caller falls back to per-entry numpy copies)."""
    lib = get()
    if lib is None or not arrays:
        return False
    dtype = arrays[0].dtype
    total = 0
    for a in arrays:
        if a.dtype != dtype or not a.flags["C_CONTIGUOUS"]:
            return False
        total += a.nbytes
    if total != out.nbytes or not out.flags["C_CONTIGUOUS"]:
        return False
    n = len(arrays)
    srcs = (ctypes.c_void_p * n)(*[a.ctypes.data for a in arrays])
    sizes = (ctypes.c_int64 * n)(*[a.nbytes for a in arrays])
    lib.hvd_pack(srcs, sizes, n, out.ctypes.data_as(ctypes.c_void_p))
    return True


def unpack_into(src, outs) -> bool:
    """Scatter a packed buffer into preallocated per-entry arrays with
    one native call (the fusion-buffer MemcpyOut without intermediate
    byte objects). ``src`` must be C-contiguous and exactly the
    concatenation of ``outs``. Returns False on fallback."""
    lib = get()
    if lib is None or not outs:
        return False
    total = 0
    for o in outs:
        if not o.flags["C_CONTIGUOUS"] or not o.flags["WRITEABLE"]:
            return False
        total += o.nbytes
    if total != src.nbytes or not src.flags["C_CONTIGUOUS"]:
        return False
    n = len(outs)
    dsts = (ctypes.c_void_p * n)(*[o.ctypes.data for o in outs])
    sizes = (ctypes.c_int64 * n)(*[o.nbytes for o in outs])
    lib.hvd_unpack(src.ctypes.data_as(ctypes.c_void_p), sizes, n, dsts)
    return True


def compiler_available() -> bool:
    """True when a C++ compiler the Makefile can drive is on PATH —
    the tier-1 gate between 'fail the build loudly' and 'skip native
    tests with a reason'."""
    import shutil
    return any(shutil.which(c) for c in ("g++", "c++", "clang++"))


def rebuild() -> None:
    """Build the library from the sources on disk whatever is already
    there, and forget any library loaded so far. For entry points that
    must not trust a library they find (chip_smoke.py); call before
    the runtime starts."""
    global _lib, _tried
    with _lock:
        _lib, _tried = None, False
        if not disabled_via_env():
            _build(force=True)


def build_status():
    """(loaded, reason) for CI plumbing: attempt the normal get() path
    and explain a None result. Used by tests/conftest.py to build the
    library once up front and fail LOUDLY when a compiler exists but
    the build is broken (a silent skip would unhook every native test
    from CI forever)."""
    lib = get()
    if lib is not None:
        return True, ""
    if disabled_via_env():
        return False, "disabled via HOROVOD_NATIVE/HOROVOD_TPU_NATIVE"
    if not compiler_available():
        return False, "no C++ compiler on PATH"
    return False, "build or load failed with a compiler present"


def cast_into(src, dst) -> bool:
    """dst[:] = src with a dtype cast via the native kernel (the
    wire-compression leg: f32<->bf16/f16). Returns False when the
    native path cannot serve this pair (caller falls back to numpy
    casting)."""
    lib = get()
    if lib is None:
        return False
    sc = _DTYPE_CODES.get(str(src.dtype))
    dc = _DTYPE_CODES.get(str(dst.dtype))
    if sc is None or dc is None or src.size != dst.size \
            or not src.flags["C_CONTIGUOUS"] \
            or not dst.flags["C_CONTIGUOUS"]:
        return False
    rc = lib.hvd_cast(
        src.ctypes.data_as(ctypes.c_void_p),
        dst.ctypes.data_as(ctypes.c_void_p),
        src.size, sc, dc)
    return rc == 0


def sum_into(acc, src) -> bool:
    """acc += src elementwise via the native kernel. Returns False if
    the native path is unavailable for this dtype (caller falls back)."""
    lib = get()
    if lib is None:
        return False
    code = _DTYPE_CODES.get(str(acc.dtype))
    if code is None or not acc.flags["C_CONTIGUOUS"] \
            or not src.flags["C_CONTIGUOUS"]:
        return False
    rc = lib.hvd_sum_into(
        acc.ctypes.data_as(ctypes.c_void_p),
        src.ctypes.data_as(ctypes.c_void_p),
        acc.size, code)
    return rc == 0


# int8-codec dtype codes (hvd_quant8/hvd_dequant8's third argument).
_QUANT_CODES = {"float32": 0, "float64": 1}


def quant8(src, out, residual=None, residual_out=None) -> bool:
    """Quantize ``src`` (f32/f64) into the int8 wire layout in ``out``
    (uint8, 4 + src.size bytes) with the native kernel: scale scan,
    saturating round-half-even and the error-feedback residual update
    fused into one pass, bit-identical to the numpy reference in
    common/wire_dtype.py. ``residual`` is added lane-wise before
    quantizing and ``residual_out`` (may alias ``residual``) receives
    the post-quantization error. Returns False when the native path
    cannot serve this call (caller falls back to numpy)."""
    lib = get()
    if lib is None:
        return False
    code = _QUANT_CODES.get(str(src.dtype))
    if code is None or not src.flags["C_CONTIGUOUS"] \
            or not out.flags["C_CONTIGUOUS"] \
            or out.dtype.itemsize != 1 or out.nbytes != 4 + src.size:
        return False
    res_p = None
    res_out_p = None
    if residual is not None:
        if residual.dtype != src.dtype or residual.size != src.size \
                or not residual.flags["C_CONTIGUOUS"] \
                or residual_out is None:
            return False
        res_p = ctypes.c_void_p(residual.ctypes.data)
    if residual_out is not None:
        if residual_out.dtype != src.dtype \
                or residual_out.size != src.size \
                or not residual_out.flags["C_CONTIGUOUS"]:
            return False
        res_out_p = ctypes.c_void_p(residual_out.ctypes.data)
    rc = lib.hvd_quant8(
        ctypes.c_void_p(src.ctypes.data), src.size, code,
        res_p, res_out_p,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return rc == 0


def dequant8(raw, out) -> bool:
    """Expand the int8 wire layout in ``raw`` (uint8, 4 + out.size
    bytes) into ``out`` (f32/f64) with the native kernel — the numpy
    astype/multiply round-trip collapsed into one pass, bit-identical.
    Returns False when the native path cannot serve this call."""
    lib = get()
    if lib is None:
        return False
    code = _QUANT_CODES.get(str(out.dtype))
    if code is None or not raw.flags["C_CONTIGUOUS"] \
            or not out.flags["C_CONTIGUOUS"] \
            or raw.dtype.itemsize != 1 or raw.nbytes < 4 + out.size:
        return False
    rc = lib.hvd_dequant8(
        raw.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.size, code, ctypes.c_void_p(out.ctypes.data))
    return rc == 0


def build_flags() -> int:
    """Capability bitmask of the loaded core (hvd_build_flags): bit 0
    io_uring compiled in, bit 1 the running kernel accepts it, bit 2
    MSG_ZEROCOPY sends compiled in. 0 without the native core."""
    lib = get()
    if lib is None:
        return 0
    return int(lib.hvd_build_flags())
