"""hvdlint (tools/hvdlint) + runtime lockdep (common/lockdep.py).

Two tiers in one module, both fast/in-process (pytest.mark.lint):

* the PROJECT gate — all nine analyzers over ``horovod_tpu/`` must
  report zero findings (this is the tier-1 rendering of the
  acceptance bar `python -m tools.hvdlint horovod_tpu` exits 0);
* per-analyzer FIXTURES — for every analyzer, a known-bad snippet that
  must fire and a known-good twin that must stay silent, proving each
  detection is real rather than vacuously green;
* real-tree MUTATION tests — each seeded historical bug class (and
  each true positive this suite ever fixed) is textually reintroduced
  into a scratch copy of the package and the analyzer must re-find it,
  proving the gate is live on the shipped code, not just on fixtures;
* the ``--changed`` cache — whole-tree replay semantics and every
  invalidation trigger (edit, rename, pragma tweak, analyzer change);
* runtime lockdep unit tests — inversion raise/warn/count semantics,
  condition-variable transparency, metrics mirror.
"""

import contextlib
import glob
import json
import os
import shutil
import subprocess
import sys
import textwrap
import threading

import pytest

from tools.hvdlint import Finding, core, lint_paths
from tools.hvdlint.core import Project

pytestmark = [pytest.mark.lint, pytest.mark.interpreter_of_its_own]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint_snippet(tmp_path, code: str, analyzer: str, name="mod.py",
                  docs: dict = None):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / name).write_text(textwrap.dedent(code))
    if docs:
        d = tmp_path / "docs"
        d.mkdir(exist_ok=True)
        for fn, content in docs.items():
            (d / fn).write_text(content)
    return lint_paths([str(pkg)], [analyzer])


# -- the tree is read once --------------------------------------------------

@pytest.fixture(scope="module", autouse=True)
def parse_once():
    """Every ``Project`` of this module parses a file's text once, so a
    mutation test parses again only the file it mutated. The index and
    the analyzers are cross-module and still run over the whole tree."""
    parsed = {}
    real = core.SourceFile

    def source_file(path, modname, text):
        key = (path, modname, text)
        if key not in parsed:
            parsed[key] = real(path, modname, text)
        return parsed[key]

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(core, "SourceFile", source_file)
        yield


@pytest.fixture(scope="module")
def tree_report():
    """Every analyzer over the real package, once, through the CLI."""
    return subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", "horovod_tpu", "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120)


def _tree_findings(tree_report, analyzer=None):
    return [Finding(**d) for d in json.loads(tree_report.stdout)["findings"]
            if analyzer in (None, d["analyzer"])]


@pytest.fixture(scope="module")
def tree_project():
    return Project([os.path.join(REPO, "horovod_tpu")])


@contextlib.contextmanager
def _undecorated(project, *qualnames):
    """``project`` with the decorators of ``qualnames`` stripped."""
    infos = [project.index.functions[qn] for qn in qualnames]
    kept = [info.decorators for info in infos]
    for info in infos:
        info.decorators = set()
    try:
        yield
    finally:
        for info, decorators in zip(infos, kept):
            info.decorators = decorators


# -- the project gate -------------------------------------------------------

def test_tree_is_clean(tree_report):
    """Every analyzer over the real package: zero findings. A finding
    here means either a real new bug (fix it) or an intentional
    pattern (suppress WITH a justification, or extend the analyzer's
    allowlist — both reviewed changes)."""
    findings = _tree_findings(tree_report)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_cli_json_exit_codes(tmp_path, tree_report):
    out = tree_report
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["count"] == 0 and payload["findings"] == []

    bad = tmp_path / "bad.py"
    bad.write_text("import os\nX = os.environ.get('HOROVOD_FOO')\n")
    out = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", str(bad), "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 1
    payload = json.loads(out.stdout)
    assert payload["count"] == 1
    assert payload["findings"][0]["analyzer"] == "knobs"

    out = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", "-a", "no-such", str(bad)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2


# -- lock-order -------------------------------------------------------------

BAD_LOCK_CYCLE = """
    import threading

    class A:
        def __init__(self):
            self._la = threading.Lock()
            self._lb = threading.Lock()

        def ab(self):
            with self._la:
                with self._lb:
                    pass

        def ba(self):
            with self._lb:
                with self._la:
                    pass
"""

GOOD_LOCK_ORDER = """
    import threading

    class A:
        def __init__(self):
            self._la = threading.Lock()
            self._lb = threading.Lock()

        def ab(self):
            with self._la:
                with self._lb:
                    pass

        def ab2(self):
            with self._la:
                with self._lb:
                    pass
"""


def test_lock_order_cycle_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_LOCK_CYCLE, "lock-order")
    assert any("cycle" in f.message for f in fs), fs


def test_lock_order_consistent_is_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_LOCK_ORDER, "lock-order") == []


def test_lock_order_blocking_under_lock(tmp_path):
    code = """
        import threading
        import time

        class A:
            def __init__(self):
                self._l = threading.Lock()

            def bad(self):
                with self._l:
                    time.sleep(1)

            def good(self):
                with self._l:
                    x = 1
                time.sleep(1)
    """
    fs = _lint_snippet(tmp_path, code, "lock-order")
    assert len(fs) == 1 and "time.sleep" in fs[0].message, fs


def test_lock_order_interprocedural_blocking(tmp_path):
    """Blocking reached through a resolved call chain, not directly."""
    code = """
        import queue
        import threading

        class A:
            def __init__(self):
                self._l = threading.Lock()
                self._queue = queue.Queue()

            def outer(self):
                with self._l:
                    self.inner()

            def inner(self):
                self._queue_wait()

            def _queue_wait(self):
                self._queue.get()
    """
    fs = _lint_snippet(tmp_path, code, "lock-order")
    assert any("may block" in f.message and "outer" in f.message
               for f in fs), fs


def test_lock_order_cv_wait_on_own_lock_is_fine(tmp_path):
    code = """
        import threading

        class H:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)

            def wait(self):
                with self._cv:
                    self._cv.wait_for(lambda: True)
    """
    assert _lint_snippet(tmp_path, code, "lock-order") == []


def test_lock_order_self_deadlock_through_call(tmp_path):
    code = """
        import threading

        class A:
            def __init__(self):
                self._l = threading.Lock()

            def outer(self):
                with self._l:
                    self.inner()

            def inner(self):
                with self._l:
                    pass
    """
    fs = _lint_snippet(tmp_path, code, "lock-order")
    assert any("self-deadlock" in f.message for f in fs), fs


def test_lock_order_suppression_needs_justification(tmp_path):
    code = """
        import threading
        import time

        _l = threading.Lock()

        def bad():
            with _l:
                time.sleep(1)  # hvdlint: disable=lock-order -- boot-only path, single-threaded by contract

        def bad2():
            with _l:
                time.sleep(2)  # hvdlint: disable=lock-order
    """
    fs = _lint_snippet(tmp_path, code, "lock-order")
    # first suppression holds; the bare one is rejected AND the finding
    # on its line is still silenced only by a VALID pragma
    assert any(f.analyzer == "pragma" for f in fs), fs
    assert sum(1 for f in fs if f.analyzer == "lock-order") == 0, fs


# -- wire-protocol ----------------------------------------------------------

BAD_WIRE = """
    import struct

    FRAME_FULL = 0
    FRAME_AGG = 2
    PACKED_PREFIX = b"\\x02"

    def serialize_thing(x):
        return bytes((FRAME_FULL,)) + x

    def parse_thing(data):
        kind = struct.unpack_from("<B", data, 0)[0]
        if kind != FRAME_FULL:
            raise ConnectionError(kind)
        return data[1:]

    def serialize_orphan(x):
        return x
"""

GOOD_WIRE = """
    import struct

    FRAME_FULL = 0
    FRAME_AGG = 2
    PACKED_PREFIX = b"\\xfe"

    def serialize_thing(x, agg=False):
        return bytes((FRAME_AGG if agg else FRAME_FULL,)) + x

    def parse_thing(data):
        if len(data) < 1:
            raise ConnectionError("truncated")
        kind = struct.unpack_from("<B", data, 0)[0]
        if kind not in (FRAME_FULL, FRAME_AGG):
            raise ConnectionError(kind)
        return data[1:]
"""


def test_wire_protocol_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_WIRE, "wire-protocol",
                       name="wire.py")
    msgs = "\n".join(f.message for f in fs)
    assert "collides with frame discriminator FRAME_AGG" in msgs
    assert "no matching parse_orphan" in msgs
    assert "not dominated by a buffer-length guard" in msgs
    # FRAME_AGG never parsed/serialized both ways? it IS unused in
    # parse — the coverage check fires too
    assert "never appears in any parse" in msgs


def test_wire_protocol_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_WIRE, "wire-protocol",
                         name="wire.py") == []


def test_wire_protocol_scopes_to_wire_modules(tmp_path):
    # the same unguarded unpack in a non-wire module is out of scope
    assert _lint_snippet(tmp_path, BAD_WIRE, "wire-protocol",
                         name="codec.py") == []


BAD_WIRE_CODES = """
    WIRE_NONE = 0
    WIRE_BF16 = 1
    WIRE_FP16 = 1
    ALG_DEFAULT = 0
    ALG_RING = 300
"""

GOOD_WIRE_CODES = """
    WIRE_NONE = 0
    WIRE_BF16 = 1
    WIRE_NAMES = 1  # name tables are exempt, not codes
    ALG_DEFAULT = 0
    ALG_STAR = 1
"""


def test_wire_protocol_code_family_collision_fires(tmp_path):
    """The negotiated-attribute families (WIRE_*/ALG_* — the wire
    dtype and algorithm bytes Requests/Responses carry) must stay
    pairwise distinct per family and u8-ranged."""
    fs = _lint_snippet(tmp_path, BAD_WIRE_CODES, "wire-protocol",
                       name="wire_dtype.py")
    msgs = "\n".join(f.message for f in fs)
    assert "WIRE_BF16 and WIRE_FP16 share byte value" in msgs
    assert "ALG_RING = 300 does not fit the u8" in msgs


def test_wire_protocol_code_family_clean(tmp_path):
    # same family value reused across DIFFERENT families is fine
    # (WIRE_BF16 == ALG_STAR == 1): the families ride distinct bytes
    assert _lint_snippet(tmp_path, GOOD_WIRE_CODES, "wire-protocol",
                         name="wire_dtype.py") == []


BAD_TRACE_CODES = """
    SPAN_SLICE = 0
    SPAN_MARK = 0
    EV_CYCLE = 0
    EV_ABORT = 1
    EV_ELASTIC = 1
    EV_NAMES = 1  # name tables exempt
"""


def test_wire_protocol_trace_code_families_fire(tmp_path):
    """The PR 11 families — SPAN_* trace span kinds and EV_* flight
    recorder event codes — join the same distinctness contract: a
    collision silently aliases two meanings in every TRACE frame and
    every postmortem ring."""
    fs = _lint_snippet(tmp_path, BAD_TRACE_CODES, "wire-protocol",
                       name="wire.py")
    msgs = "\n".join(f.message for f in fs)
    assert "SPAN_SLICE and SPAN_MARK share byte value" in msgs
    assert "EV_ABORT and EV_ELASTIC share byte value" in msgs


BAD_TENANT_CODES = """
    TENANT_ATTACH = 0
    TENANT_LEASE = 0
    TENANT_NAMES = 0  # name tables exempt
    TENANT_BIG = 300
"""


def test_wire_protocol_tenant_code_family_fires(tmp_path):
    """The TENANT_* service-plane frame kinds (common/tenancy.py
    attach/lease/snapshot protocol) join the distinctness contract —
    an aliased kind byte would let one gate frame decode as another."""
    fs = _lint_snippet(tmp_path, BAD_TENANT_CODES, "wire-protocol",
                       name="wire.py")
    msgs = "\n".join(f.message for f in fs)
    assert "TENANT_ATTACH and TENANT_LEASE share byte value" in msgs
    assert "TENANT_BIG = 300 does not fit the u8" in msgs


def test_wire_protocol_real_tenant_codes_distinct():
    """Anchor the real tree: every TENANT_* kind in wire.py is
    distinct and u8-ranged (the analyzer gate proves itself on the
    fixture above; this proves the SHIPPED codes)."""
    from horovod_tpu.common import wire
    codes = {n: getattr(wire, n) for n in dir(wire)
             if n.startswith("TENANT_") and not n.endswith("NAMES")
             and not n.endswith("PREFIX")
             and isinstance(getattr(wire, n), int)}
    assert len(codes) >= 6, codes
    assert len(set(codes.values())) == len(codes), codes
    assert all(0 <= v <= 255 for v in codes.values()), codes


BAD_ALG_CODES = """
    ALG_DEFAULT = 0
    ALG_STAR = 1
    ALG_TWOLEVEL = 3
    ALG_NEXT = 3
    ALG_HUGE = 300
"""


def test_wire_protocol_new_alg_joins_family_distinctness(tmp_path):
    """A new ALG_* verdict rides the same negotiated u8 algorithm
    byte as star/ring/two-level — a collision would make the
    coordinator's stamp decode as another topology on every peer."""
    fs = _lint_snippet(tmp_path, BAD_ALG_CODES, "wire-protocol",
                       name="wire_dtype.py")
    msgs = "\n".join(f.message for f in fs)
    assert "ALG_TWOLEVEL and ALG_NEXT share byte value" in msgs
    assert "ALG_HUGE = 300 does not fit the u8" in msgs


def test_wire_protocol_real_alg_codes_distinct():
    """Anchor the real tree: every shipped ALG_* verdict code in
    wire_dtype.py is pairwise distinct and u8-ranged."""
    from horovod_tpu.common import wire_dtype as wd
    codes = {n: getattr(wd, n) for n in dir(wd)
             if n.startswith("ALG_") and not n.endswith("NAMES")
             and isinstance(getattr(wd, n), int)}
    assert len(codes) >= 4, codes          # default/star/ring/2lvl
    assert len(set(codes.values())) == len(codes), codes
    assert all(0 <= v <= 255 for v in codes.values()), codes


BAD_CONTROLLER_TAGS = """
    TAG_HANDSHAKE = 1
    TAG_REQUESTS = 2
    TAG_TRACE = 2
    TAG_BIG = 999
"""

GOOD_CONTROLLER_TAGS = """
    TAG_HANDSHAKE = 1
    TAG_REQUESTS = 2
    TAG_METRICS = 7
    TAG_TRACE = 8
"""


def test_wire_protocol_controller_tag_collision_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_CONTROLLER_TAGS, "wire-protocol",
                       name="controller.py")
    msgs = "\n".join(f.message for f in fs)
    assert "TAG_REQUESTS and TAG_TRACE share byte value" in msgs
    assert "TAG_BIG = 999 does not fit the u8" in msgs


def test_wire_protocol_controller_tags_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_CONTROLLER_TAGS,
                         "wire-protocol", name="controller.py") == []


def test_trace_frame_codec_real_tree_guarded(tmp_path):
    """The REAL wire.py trace codec passes the analyzer — pairing
    (serialize_/parse_trace_frame), guard domination, and family
    distinctness all hold on the shipped tree (the clean-tree gate
    covers this too; this pins the specific module)."""
    import shutil
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    shutil.copy(os.path.join(REPO, "horovod_tpu", "common", "wire.py"),
                pkg / "wire.py")
    assert lint_paths([str(pkg)], ["wire-protocol"]) == []


# -- native-codec -----------------------------------------------------------

_NATIVE_HEADER = """
    #pragma once
    #include <cstdint>
    extern "C" {
    int hvd_sum_into(void* acc, const void* src, int64_t count,
                     int dtype);
    int hvd_gather_frames(const int* fds, int n, const uint8_t* secret,
                          int secret_len, uint8_t** bufs, int64_t* lens,
                          uint8_t* tags, int timeout_ms);
    void hvd_free(uint8_t* buf);
    int hvd_orphan(int fd, void (*cb)(void), int n);
    }
"""

BAD_NATIVE_LOADER = """
    import ctypes

    def _configure(lib):
        # arity drift: C declares 4 params, mirror lists 3
        lib.hvd_sum_into.restype = ctypes.c_int
        lib.hvd_sum_into.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
        # argtypes without restype
        lib.hvd_gather_frames.argtypes = [
            ctypes.POINTER(ctypes.c_int), ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int]
        lib.hvd_free.restype = None
        lib.hvd_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
        # configured but not declared anywhere
        lib.hvd_ghost.restype = ctypes.c_int
        lib.hvd_ghost.argtypes = [ctypes.c_int]

    def gather(lib, fds):
        # allocating entry point with no hvd_free anywhere in sight
        return lib.hvd_gather_frames(fds, 1, None, 0, None, None,
                                     None, -1)
"""

GOOD_NATIVE_LOADER = """
    import ctypes

    def _configure(lib):
        lib.hvd_sum_into.restype = ctypes.c_int
        lib.hvd_sum_into.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int]
        lib.hvd_gather_frames.restype = ctypes.c_int
        lib.hvd_gather_frames.argtypes = [
            ctypes.POINTER(ctypes.c_int), ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int]
        lib.hvd_free.restype = None
        lib.hvd_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
        lib.hvd_orphan.restype = ctypes.c_int
        lib.hvd_orphan.argtypes = [ctypes.c_int, ctypes.c_void_p,
                                   ctypes.c_int]

    def gather(lib, fds, bufs):
        rc = lib.hvd_gather_frames(fds, 1, None, 0, bufs, None,
                                   None, -1)
        for b in bufs:
            lib.hvd_free(b)
        return rc
"""


def _lint_native(tmp_path, loader_code: str, header: str = None):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "native.py").write_text(textwrap.dedent(loader_code))
    native_dir = tmp_path / "native"
    native_dir.mkdir(exist_ok=True)
    (native_dir / "hvdtpu.h").write_text(
        textwrap.dedent(header or _NATIVE_HEADER))
    return lint_paths([str(pkg)], ["native-codec"])


def test_native_codec_fires(tmp_path):
    fs = _lint_native(tmp_path, BAD_NATIVE_LOADER)
    msgs = "\n".join(f.message for f in fs)
    assert "argtypes lists 3 parameters but the C declaration has 4" \
        in msgs
    assert "hvd_gather_frames has argtypes but no restype" in msgs
    assert "hvd_orphan is declared" in msgs  # unmirrored entry point
    assert "hvd_ghost is configured for ctypes but not declared" in msgs
    assert "never references hvd_free" in msgs


def test_native_codec_clean(tmp_path):
    assert _lint_native(tmp_path, GOOD_NATIVE_LOADER) == []


def test_native_codec_function_pointer_arity(tmp_path):
    """A function-pointer parameter's own parentheses must not split
    the C parameter count (the hvd_steady_coord on_idle shape)."""
    from tools.hvdlint.native_codec import parse_header
    decls = parse_header(textwrap.dedent(_NATIVE_HEADER))
    assert decls["hvd_orphan"] == 3


def test_native_codec_tag_distinctness(tmp_path):
    code = """
        TAG_A = 1
        TAG_B = 1
        TAG_BIG = 300
    """
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "controller.py").write_text(textwrap.dedent(code))
    fs = lint_paths([str(pkg)], ["native-codec"])
    msgs = "\n".join(f.message for f in fs)
    assert "TAG_A and TAG_B share byte value" in msgs
    assert "does not fit the u8 tag byte" in msgs


def test_native_codec_real_tree_mirror(tree_report):
    """The REAL loader must mirror the REAL header exactly — this is
    the check that catches a future C signature change whose author
    forgot the ctypes side."""
    from tools.hvdlint.native_codec import parse_header
    header = os.path.join(REPO, "native", "hvdtpu.h")
    with open(header) as fh:
        decls = parse_header(fh.read())
    # every entry point this PR leans on is visible to the analyzer
    for fn in ("hvd_sendv", "hvd_recv_into", "hvd_steady_worker",
               "hvd_steady_worker_chunked", "hvd_steady_coord",
               "hvd_sum_into", "hvd_cast",
               # the kernel-side wire-speed additions
               "hvd_gather_frames_batched", "hvd_sendv_zc",
               "hvd_relay_frame", "hvd_quant8", "hvd_dequant8",
               "hvd_build_flags"):
        assert fn in decls, fn
    fs = _tree_findings(tree_report, "native-codec")
    assert fs == [], "\n".join(f.render() for f in fs)


BAD_REACTOR_DRIVER = """
    import ctypes

    def gather_batched(lib, fds, n):
        dev = ctypes.POINTER(ctypes.c_uint8)()
        return lib.hvd_gather_frames_batched(fds, n, ctypes.byref(dev))

    def relay(lib, up_fd, kids):
        spill = ctypes.POINTER(ctypes.c_uint8)()
        return lib.hvd_relay_frame(up_fd, kids, ctypes.byref(spill))
"""


def test_native_codec_reactor_entry_points_allocating(tmp_path):
    """The reactor entry points spill malloc'd frames back to Python
    (batched-gather deviations, relay oversize/deviation payloads) —
    a driver that consumes them without hvd_free is the same
    per-cycle leak as a gather_frames driver."""
    fs = _lint_native(tmp_path, BAD_REACTOR_DRIVER)
    msgs = "\n".join(f.message for f in fs)
    assert "gather_batched calls hvd_gather_frames_batched" in msgs
    assert "relay calls hvd_relay_frame" in msgs


def test_wire_truncated_frames_raise_connectionerror():
    """The fix the analyzer demanded: every decoder surfaces a
    truncated buffer as ConnectionError, never struct.error/IndexError
    or a silently-wrong mask."""
    import numpy as np

    from horovod_tpu.common import wire
    from horovod_tpu.common.message import (
        CacheCycleRequest, Request, RequestList, RequestType, DataType,
    )

    req = Request(request_rank=0, request_type=RequestType.ALLREDUCE,
                  tensor_type=DataType.FLOAT32, tensor_name="t",
                  tensor_shape=(4, 4))
    full = wire.serialize_cycle_request(RequestList([req], False))
    cached = wire.serialize_cycle_request(CacheCycleRequest(
        epoch=3, nslots=64, hit_mask=(1 << 63) | 5,
        spec_payload=[(DataType.FLOAT32,
                       np.ones(8, np.float32).tobytes())]))
    metrics = wire.serialize_metrics_frame(
        1, {"c": {"k": "c", "v": 1.0},
            "h": {"k": "h", "bounds": [0.1], "counts": [1, 2],
                  "sum": 0.5, "count": 3}})
    for blob, parse in ((full, wire.parse_cycle_request),
                        (cached, wire.parse_cycle_request),
                        (metrics, wire.parse_metrics_frame)):
        parse(blob)  # intact roundtrip sanity
        for cut in range(1, len(blob)):
            try:
                parse(blob[:cut])
            except (ConnectionError, ValueError):
                pass  # ValueError: metrics version byte path
            # no struct.error, no IndexError, no silent success with
            # a wrong mask REQUIRED — silent success is only legal if
            # the truncation removed nothing the parser reads
    # the mask specifically must never silently truncate
    with pytest.raises(ConnectionError):
        wire.parse_cycle_request(cached[:15])


# -- world-coherence --------------------------------------------------------

BAD_COHERENCE = """
    class Cache:
        def __init__(self):
            self.epoch = 0  # hvdlint: world-replicated

        def put(self, k):
            self.epoch += 1

    class Runtime:
        def __init__(self):
            self._cache = Cache()

        def local_poke(self):
            self._cache.put("x")
"""

GOOD_COHERENCE = """
    from horovod_tpu.common.invariants import world_coherent

    class Cache:
        def __init__(self):
            self.epoch = 0  # hvdlint: world-replicated

        def put(self, k):
            self.epoch += 1

    class Runtime:
        def __init__(self):
            self._cache = Cache()

        @world_coherent
        def apply_verdict(self):
            self._cache.put("x")
"""


def test_world_coherence_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_COHERENCE, "world-coherence")
    msgs = "\n".join(f.message for f in fs)
    assert "world-replicated" in msgs and "Cache.put" in msgs, fs


def test_world_coherence_annotated_is_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_COHERENCE,
                         "world-coherence") == []


def test_world_coherence_decorator_is_load_bearing(tree_project):
    """Stripping @world_coherent from the runtime's verdict applier
    must fail the real tree — the annotation is what the analyzer
    anchors trust to, not a comment."""
    from tools.hvdlint import world_coherence
    p = tree_project
    with _undecorated(
            p, "horovod_tpu.common.runtime.Runtime._apply_cached_cycle"):
        fs = world_coherence.run(p)
    assert any("world-replicated" in f.message for f in fs), fs


# A rank-local mutation of the elastic membership (the PR 8 rank
# table / generation / blacklist) — the exact divergence class the
# elastic re-rendezvous must never allow: one rank editing its own
# view of who is in the world outside a broadcast verdict.
BAD_ELASTIC_COHERENCE = """
    class Membership:
        def __init__(self):
            self.rank_table = {}  # hvdlint: world-replicated
            self.generation = 0  # hvdlint: world-replicated

        def install(self, gen, table):
            self.rank_table = dict(table)
            self.generation = gen

    class Recovery:
        def __init__(self):
            self._membership = Membership()

        def handle_timeout(self, dead_rank):
            # rank-LOCAL guess: drops a member without a verdict
            self._membership.install(
                self._membership.generation + 1, {})
"""


def test_world_coherence_fires_on_local_elastic_mutation(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_ELASTIC_COHERENCE,
                       "world-coherence")
    msgs = "\n".join(f.message for f in fs)
    assert "world-replicated" in msgs and "Membership.install" in msgs, fs


def test_world_coherence_real_elastic_membership_is_anchored(tree_project):
    """The REAL elastic Membership.install must carry the
    @world_coherent anchor — stripping it fails the tree, proving the
    rank table / generation / blacklist can only move behind
    broadcast-identical inputs."""
    from tools.hvdlint import world_coherence
    p = tree_project
    qn = "horovod_tpu.common.elastic.Membership.install"
    assert qn in p.index.functions, sorted(
        k for k in p.index.functions if "elastic" in k)[:20]
    # apply_membership is covered only through its own decorator;
    # strip that too so coverage cannot flow around the mutator.
    with _undecorated(
            p, qn,
            "horovod_tpu.common.elastic.ElasticContext.apply_membership"):
        fs = world_coherence.run(p)
    assert any("Membership" in f.message
               and "world-replicated" in f.message for f in fs), fs


# A rank-local mutation of an overlap in-flight cycle table — the
# divergence class the overlap tier must never allow: one rank
# reordering (or locally appending to) its submitted-cycle sequence
# outside the world-identically-built submission path, which would
# desynchronize the strictly-FIFO wire order peers rely on.
BAD_OVERLAP_COHERENCE = """
    class Runtime:
        def __init__(self):
            self._inflight_masks = []  # hvdlint: world-replicated

        def requeue_priority(self, mask):
            # rank-LOCAL reorder: jumps a cycle ahead of the FIFO
            self._inflight_masks.insert(0, mask)
"""


def test_world_coherence_fires_on_local_overlap_mutation(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_OVERLAP_COHERENCE,
                       "world-coherence")
    msgs = "\n".join(f.message for f in fs)
    assert "world-replicated" in msgs \
        and "requeue_priority" in msgs, fs


def test_world_coherence_real_overlap_inflight_is_anchored(tree_project):
    """The REAL overlap submit path must carry the @world_coherent
    anchor — stripping it (and the drain-side mutators coverage could
    flow through) fails the tree, proving the in-flight cycle
    sequence only ever moves in the world-identical program order."""
    from tools.hvdlint import world_coherence
    p = tree_project
    qn = "horovod_tpu.common.runtime.Runtime._submit_overlap_cycle"
    assert qn in p.index.functions, sorted(
        k for k in p.index.functions if "overlap" in k)[:20]
    with _undecorated(p, *(
            f"horovod_tpu.common.runtime.Runtime.{fn}"
            for fn in ("_submit_overlap_cycle", "_apply_overlap_verdict",
                       "_unwind_cancelled_cycle", "_drop_inflight_mask"))):
        fs = world_coherence.run(p)
    assert any("_inflight_masks" in f.message
               and "world-replicated" in f.message for f in fs), fs


# A rank-local mutation of a tenant's scheduling descriptor — the
# divergence class multi-tenancy must never allow: one rank adopting
# its LOCAL env weight/quota instead of the coordinator-broadcast
# descriptor, so its pacing (and therefore its cycle participation)
# marches to a different drummer than its peers'.
BAD_TENANT_COHERENCE = """
    class Tenant:
        def __init__(self):
            self._desc = None  # hvdlint: world-replicated

        def apply(self, desc):
            self._desc = dict(desc)

    class Bootstrap:
        def __init__(self):
            self._tenant = Tenant()

        def from_local_env(self, env_weight):
            # rank-LOCAL source: this rank's env, not the broadcast
            self._tenant.apply({"weight": env_weight})
"""


def test_world_coherence_fires_on_local_tenant_descriptor(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_TENANT_COHERENCE,
                       "world-coherence")
    msgs = "\n".join(f.message for f in fs)
    assert "world-replicated" in msgs and "Tenant.apply" in msgs, fs


def test_world_coherence_real_tenant_descriptor_is_anchored(tree_project):
    """The REAL tenant descriptor install must carry the
    @world_coherent anchor — stripping it (and the module-level
    installer coverage could flow through) fails the tree, proving
    tenant scheduling state only ever moves on the coordinator's
    handshake broadcast."""
    from tools.hvdlint import world_coherence
    p = tree_project
    qn = "horovod_tpu.common.tenancy.Tenant._apply_descriptor"
    assert qn in p.index.functions, sorted(
        k for k in p.index.functions if "tenancy" in k)[:20]
    with _undecorated(
            p, qn, "horovod_tpu.common.tenancy._install_descriptor"):
        fs = world_coherence.run(p)
    assert any("_desc" in f.message
               and "world-replicated" in f.message for f in fs), fs


# A rank-local mutation of the supervision verdict — the divergence
# class self-operation must never allow: one rank adopting a demotion
# (and therefore pacing its cycles) that its peers never saw, instead
# of installing the descriptor carried by the resize verdict broadcast.
BAD_SELFOP_COHERENCE = """
    class SupervisionVerdict:
        def __init__(self):
            self.kind = ""  # hvdlint: world-replicated
            self.pace_us = 0  # hvdlint: world-replicated

        def install(self, kind, pace_us):
            self.kind = kind
            self.pace_us = pace_us

    class Policy:
        def __init__(self):
            self._verdict = SupervisionVerdict()

        def local_hunch(self, lag_s):
            # rank-LOCAL source: this rank's own lag estimate, not the
            # coordinator's broadcast decision
            self._verdict.install("demote", int(lag_s * 1e6))
"""


def test_world_coherence_fires_on_local_selfop_verdict(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_SELFOP_COHERENCE,
                       "world-coherence")
    msgs = "\n".join(f.message for f in fs)
    assert "world-replicated" in msgs \
        and "SupervisionVerdict.install" in msgs, fs


def test_world_coherence_real_selfop_verdict_is_anchored(tree_project):
    """The REAL SupervisionVerdict.install must carry the
    @world_coherent anchor — stripping it fails the tree, proving the
    demotion/pacing descriptor only ever moves on inputs every member
    received in the same resize verdict."""
    from tools.hvdlint import world_coherence
    p = tree_project
    qn = "horovod_tpu.common.selfop.SupervisionVerdict.install"
    assert qn in p.index.functions, sorted(
        k for k in p.index.functions if "selfop" in k)[:20]
    with _undecorated(p, qn):
        fs = world_coherence.run(p)
    assert any("SupervisionVerdict" in f.message
               and "world-replicated" in f.message for f in fs), fs


def test_world_coherent_decorator_is_identity():
    from horovod_tpu.common.invariants import world_coherent

    @world_coherent
    def f(x):
        return x + 1

    assert f(1) == 2 and f.__world_coherent__


# -- teardown ---------------------------------------------------------------

BAD_TEARDOWN = """
    class R:
        def run(self):
            try:
                pass
            finally:
                self.finalizer.drain()
                self.timeline.shutdown()
"""

GOOD_TEARDOWN = """
    class R:
        def run(self):
            try:
                pass
            finally:
                try:
                    self.finalizer.drain()
                except Exception:
                    pass
                try:
                    self.timeline.shutdown()
                except Exception:
                    pass
"""


def test_teardown_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_TEARDOWN, "teardown")
    assert len(fs) == 2, fs
    assert all("unguarded cleanup stage" in f.message for f in fs)


def test_teardown_guarded_is_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_TEARDOWN, "teardown") == []


def test_teardown_close_function_last_stage_may_raise(tmp_path):
    code = """
        class C:
            def close(self):
                try:
                    self._ch.close()
                except OSError:
                    pass
                self._server.close()
    """
    assert _lint_snippet(tmp_path, code, "teardown") == []


def test_teardown_single_stage_is_fine(tmp_path):
    code = """
        def f(path):
            fh = open(path)
            try:
                return fh.read()
            finally:
                fh.close()
    """
    assert _lint_snippet(tmp_path, code, "teardown") == []


# -- knobs ------------------------------------------------------------------

def test_knobs_direct_read_fires(tmp_path):
    code = """
        import os

        def f():
            return os.environ.get("HOROVOD_WHATEVER", "1")
    """
    fs = _lint_snippet(tmp_path, code, "knobs")
    assert any("HOROVOD_WHATEVER" in f.message
               and "outside common/config.py" in f.message for f in fs)


def test_knobs_config_module_and_writes_are_fine(tmp_path):
    code = """
        import os

        def from_env():
            return os.environ.get("HOROVOD_THING", "1")

        def launcher(v):
            os.environ["HOROVOD_CHILD"] = v
            os.environ.setdefault("HOROVOD_OTHER", "x")
    """
    fs = _lint_snippet(tmp_path, code, "knobs", name="config.py",
                       docs={"knobs.md": "HOROVOD_THING does things"})
    assert fs == [], fs


def test_knobs_undocumented_fires(tmp_path):
    code = """
        import os

        def from_env():
            return os.environ.get("HOROVOD_SECRET_HANDSHAKE", "")
    """
    fs = _lint_snippet(tmp_path, code, "knobs", name="config.py",
                       docs={"other.md": "nothing relevant"})
    assert any("appears nowhere" in f.message for f in fs), fs


# -- runtime lockdep --------------------------------------------------------

@pytest.fixture
def lockcheck():
    from horovod_tpu.common import lockdep
    lockdep.reset("raise")
    yield lockdep
    lockdep.reset()


def test_lockdep_inversion_raises(lockcheck):
    a = lockcheck.lock("t.A")
    b = lockcheck.lock("t.B")
    with a:
        with b:
            pass
    with pytest.raises(lockcheck.LockInversionError) as ei:
        with b:
            with a:
                pass
    assert "t.A" in str(ei.value) and "t.B" in str(ei.value)
    assert lockcheck.inversion_count() == 1
    # the inverting acquire was REFUSED before taking the lock: a is
    # free, so the consistent order still works afterwards
    with a:
        with b:
            pass


def test_lockdep_consistent_order_never_fires(lockcheck):
    a = lockcheck.lock("t.A")
    b = lockcheck.lock("t.B")
    errors = []

    def worker():
        try:
            for _ in range(200):
                with a:
                    with b:
                        pass
        except Exception as e:  # pragma: no cover
            errors.append(e)

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors and lockcheck.inversion_count() == 0


def test_lockdep_cross_thread_inversion(lockcheck):
    """The edge recorded by one thread convicts another — that is the
    whole point (a single thread never deadlocks with itself)."""
    a = lockcheck.lock("t.A")
    b = lockcheck.lock("t.B")

    def establish():
        with a:
            with b:
                pass

    t = threading.Thread(target=establish)
    t.start()
    t.join()
    with pytest.raises(lockcheck.LockInversionError):
        with b:
            with a:
                pass


def test_lockdep_same_class_instances_do_not_false_positive(lockcheck):
    l1 = lockcheck.lock("metrics.Counter._lock")
    l2 = lockcheck.lock("metrics.Counter._lock")
    with l1:
        with l2:
            pass
    with l2:
        with l1:
            pass
    assert lockcheck.inversion_count() == 0


def test_lockdep_condition_shares_lock_class(lockcheck):
    lk = lockcheck.lock("t.H")
    cv = lockcheck.condition("t.H", lk)
    done = []

    def waiter():
        with cv:
            cv.wait_for(lambda: bool(done), timeout=5.0)

    t = threading.Thread(target=waiter)
    t.start()
    with cv:
        done.append(1)
        cv.notify_all()
    t.join(5.0)
    assert not t.is_alive()
    assert lockcheck.inversion_count() == 0


def test_lockdep_warn_mode_counts_without_raising(capsys):
    from horovod_tpu.common import lockdep
    lockdep.reset("warn")
    try:
        a = lockdep.lock("w.A")
        b = lockdep.lock("w.B")
        with a:
            with b:
                pass
        with b:
            with a:  # warn-mode: logged + counted, not raised
                pass
        assert lockdep.inversion_count() == 1
        assert "lock-order inversion" in capsys.readouterr().err
    finally:
        lockdep.reset()


def test_lockdep_disabled_returns_plain_locks():
    from horovod_tpu.common import lockdep
    lockdep.reset("")
    try:
        lk = lockdep.lock("x")
        assert isinstance(lk, type(threading.Lock()))
    finally:
        lockdep.reset()


def test_lockdep_counter_reaches_metrics_plane(monkeypatch):
    """Satellite: an armed world surfaces inversions on the metrics
    plane — hvd_lockcheck_inversions_total mirrors
    lockdep.inversion_count() through the runtime collector."""
    import horovod_tpu as hvd
    from horovod_tpu.common import lockdep

    hvd.shutdown()
    lockdep.reset("warn")
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    try:
        a = lockdep.lock("m.A")
        b = lockdep.lock("m.B")
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        assert lockdep.inversion_count() == 1
        hvd.init()
        try:
            view = hvd.metrics()
            rec = view["local"]["hvd_lockcheck_inversions_total"]
            assert rec["v"] == 1.0, rec
            world = view["world"]["hvd_lockcheck_inversions_total"]
            assert world["v"] == 1.0, world
        finally:
            hvd.shutdown()
    finally:
        lockdep.reset()


def test_logging_lock_level_env_still_works(monkeypatch, capsys):
    """The knob rerouting kept semantics: HOROVOD_LOG_HIDE_TIME is now
    a real boolean (hvdlint: knobs), and levels still gate."""
    from horovod_tpu.common import logging as hlog
    monkeypatch.setenv("HOROVOD_LOG_HIDE_TIME", "1")
    hlog.set_level("info")
    try:
        hlog.info("knob-reroute-probe", rank=3)
        err = capsys.readouterr().err
        assert "knob-reroute-probe" in err and "[3]" in err
        assert not any(ch.isdigit() for ch in err.split("[3]")[0])
    finally:
        hlog.reset_level()


# -- CLI --list completeness ------------------------------------------------

def test_list_names_every_analyzer():
    """--list is the discovery surface: a registered analyzer missing
    here (or an unregistered module) is a silent hole in the gate."""
    from tools.hvdlint.core import get_analyzers
    out = subprocess.run(
        [sys.executable, "-m", "tools.hvdlint", "--list"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    listed = out.stdout.split()
    assert listed == sorted(get_analyzers())
    assert listed == [
        "jax_compat", "knobs", "lock-order", "native-codec",
        "native-lifetime", "teardown", "thread-ownership",
        "wire-protocol", "world-coherence"]


# -- thread-ownership -------------------------------------------------------

# check 1: compound writes from two roles, nothing ordering them
BAD_MULTI_ROLE_WRITE = """
    import threading

    class Svc:
        def __init__(self):
            self._stats = {}
            t = threading.Thread(target=self._loop,
                                 name="hvd-background", daemon=True)
            t.start()

        def _loop(self):
            self._stats["cycles"] = 1

        def public(self):
            self._stats["calls"] = 2
"""

GOOD_MULTI_ROLE_WRITE = """
    import threading

    class Svc:
        def __init__(self):
            self._lk = threading.Lock()
            self._stats = {}
            t = threading.Thread(target=self._loop,
                                 name="hvd-background", daemon=True)
            t.start()

        def _loop(self):
            with self._lk:
                self._stats["cycles"] = 1

        def public(self):
            with self._lk:
                self._stats["calls"] = 2
"""


def test_thread_ownership_multi_role_write_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_MULTI_ROLE_WRITE,
                       "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "compound writes" in msgs and "hvd-background" in msgs, fs


def test_thread_ownership_locked_writes_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_MULTI_ROLE_WRITE,
                         "thread-ownership") == []


# check 2: single writer, foreign lock-free reader, no snapshot-swap
BAD_UNPUBLISHED_WRITE = """
    import threading

    class Svc:
        def __init__(self):
            self._table = {}
            t = threading.Thread(target=self._loop,
                                 name="hvd-background", daemon=True)
            t.start()

        def _loop(self):
            self._table["x"] = 1

        def read(self):
            return self._table.get("x")
"""

# the snapshot-swap idiom: the writer rebinds a freshly built dict in
# one assignment — a lock-free reader sees old or new, never a hybrid
GOOD_SNAPSHOT_SWAP = """
    import threading

    class Svc:
        def __init__(self):
            self._table = {}
            t = threading.Thread(target=self._loop,
                                 name="hvd-background", daemon=True)
            t.start()

        def _loop(self):
            self._table = {"x": 1}

        def read(self):
            return self._table.get("x")
"""


def test_thread_ownership_unpublished_write_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_UNPUBLISHED_WRITE,
                       "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "read from role(s)" in msgs and "['main']" in msgs, fs


def test_thread_ownership_snapshot_swap_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_SNAPSHOT_SWAP,
                         "thread-ownership") == []


# check 3: the _on_arrivals shape — a rebindable hook read twice with
# a rebind possible between the reads (if self.hook: self.hook())
BAD_CAPTURE_ONCE = """
    class Svc:
        _hook = None

        def attach(self, cb):
            self._hook = cb

        def fire(self):
            if self._hook is not None:
                self._hook(1)
"""

GOOD_CAPTURE_ONCE = """
    class Svc:
        _hook = None

        def attach(self, cb):
            self._hook = cb

        def fire(self):
            hook = self._hook
            if hook is not None:
                hook(1)
"""


def test_thread_ownership_capture_once_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_CAPTURE_ONCE, "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "capture it into a local once" in msgs, fs


def test_thread_ownership_captured_hook_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_CAPTURE_ONCE,
                         "thread-ownership") == []


def test_thread_ownership_sees_through_inheritance(tmp_path):
    """A base-declared hook read from a derived-class method is the
    SAME storage — the exact split that hid the original
    Controller._on_arrivals bug from a per-class field model."""
    code = """
        class Base:
            _hook = None

            def attach(self, cb):
                self._hook = cb

        class Derived(Base):
            def fire(self):
                if self._hook is not None:
                    self._hook(1)
    """
    fs = _lint_snippet(tmp_path, code, "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "mod.Base._hook" in msgs, fs


# check 4: the mark_done shape — gate published before the payload a
# lock-free reader keys on
BAD_PUBLISH_ORDER = """
    import threading

    class Table:
        def __init__(self):
            self._lk = threading.Lock()
            self._res = {}
            self._out = {}

        def done(self, h, status, output):
            with self._lk:
                self._res[h] = status
                self._out[h] = output

        def poll(self, h):
            return self._res.get(h) is not None

        def get(self, h):
            return self._out[h]
"""

GOOD_PUBLISH_ORDER = """
    import threading

    class Table:
        def __init__(self):
            self._lk = threading.Lock()
            self._res = {}
            self._out = {}

        def done(self, h, status, output):
            with self._lk:
                self._out[h] = output
                self._res[h] = status

        def poll(self, h):
            return self._res.get(h) is not None

        def get(self, h):
            return self._out[h]
"""


def test_thread_ownership_publish_order_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_PUBLISH_ORDER, "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "before storing payload" in msgs, fs


def test_thread_ownership_payload_first_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_PUBLISH_ORDER,
                         "thread-ownership") == []


def test_thread_ownership_pragma_suppresses_with_justification(tmp_path):
    code = BAD_MULTI_ROLE_WRITE.replace(
        'self._stats["calls"] = 2',
        'self._stats["calls"] = 2  '
        '# hvdlint: owned-by=main -- single-writer in this app')
    assert _lint_snippet(tmp_path, code, "thread-ownership") == []


def test_thread_ownership_pragma_requires_justification(tmp_path):
    code = BAD_MULTI_ROLE_WRITE.replace(
        'self._stats["calls"] = 2',
        'self._stats["calls"] = 2  # hvdlint: owned-by=main')
    fs = _lint_snippet(tmp_path, code, "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "justification" in msgs, fs


# -- native-lifetime --------------------------------------------------------

BAD_INLINE_TEMPORARY = """
    import ctypes
    import numpy as np

    def call(lib, x):
        lib.hvd_pack(np.ascontiguousarray(x).ctypes.data_as(
            ctypes.c_void_p))
"""

GOOD_NAMED_BUFFER = """
    import ctypes
    import numpy as np

    def call(lib, x):
        buf = np.ascontiguousarray(x)
        lib.hvd_pack(buf.ctypes.data_as(ctypes.c_void_p))
"""


def test_native_lifetime_inline_temporary_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_INLINE_TEMPORARY,
                       "native-lifetime")
    msgs = "\n".join(f.message for f in fs)
    assert "unnamed temporary" in msgs, fs


def test_native_lifetime_named_buffer_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_NAMED_BUFFER,
                         "native-lifetime") == []


BAD_TEMP_CALLBACK = """
    import ctypes

    ON_IDLE = ctypes.CFUNCTYPE(None)

    def install(lib, f):
        lib.hvd_set_idle(ON_IDLE(f))
"""

GOOD_OWNED_CALLBACK = """
    import ctypes

    ON_IDLE = ctypes.CFUNCTYPE(None)

    class Hooks:
        def install(self, lib, f):
            self._cb = ON_IDLE(f)
            lib.hvd_set_idle(self._cb)
"""


def test_native_lifetime_temp_callback_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_TEMP_CALLBACK, "native-lifetime")
    msgs = "\n".join(f.message for f in fs)
    assert "CFUNCTYPE" in msgs, fs


def test_native_lifetime_owned_callback_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_OWNED_CALLBACK,
                         "native-lifetime") == []


BAD_ARENA_CACHE = """
    import ctypes

    class Ring:
        def __init__(self):
            self._ptr_cache = {}

        def send(self, arena, n):
            buf = arena.ensure(n)
            key = ("send", n)
            c = self._ptr_cache.get(key)
            if c is None:
                c = buf.ctypes.data_as(ctypes.c_void_p)
                self._ptr_cache[key] = c
            return c
"""

GOOD_ARENA_CACHE = """
    import ctypes

    class Ring:
        def __init__(self):
            self._ptr_cache = {}

        def send(self, arena, n):
            buf = arena.ensure(n)
            key = ("send", n, arena.generation)
            c = self._ptr_cache.get(key)
            if c is None:
                c = buf.ctypes.data_as(ctypes.c_void_p)
                self._ptr_cache[key] = c
            return c
"""


def test_native_lifetime_arena_cache_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_ARENA_CACHE, "native-lifetime")
    msgs = "\n".join(f.message for f in fs)
    assert "generation" in msgs, fs


def test_native_lifetime_generation_keyed_cache_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_ARENA_CACHE,
                         "native-lifetime") == []


GOOD_REACTOR_IDLE_CACHE = """
    import ctypes

    ON_IDLE = ctypes.CFUNCTYPE(None)

    class Fanout:
        def __init__(self):
            self._on_idle_c = None

        def gather(self, lib, f):
            if self._on_idle_c is None:
                self._on_idle_c = ON_IDLE(f)
            lib.hvd_gather_frames_batched(self._on_idle_c)
"""


def test_native_lifetime_reactor_idle_cache_clean(tmp_path):
    """The batched reactor's lazily-built, self-owned ON_IDLE thunk
    (the _NativeFanout.gather_into shape): cached on the instance, so
    the callback object outlives the native call that fires it — the
    analyzer must accept it, only temporaries fire."""
    assert _lint_snippet(tmp_path, GOOD_REACTOR_IDLE_CACHE,
                         "native-lifetime") == []


# -- real-tree mutation gates ----------------------------------------------
# Each test reintroduces one shipped (or would-ship) bug into a scratch
# copy of the package and asserts the analyzer re-finds it — the proof
# that the gate bites on the real tree, not just on fixtures. The
# mutated shapes are the three historical bug classes from the module
# docstring of tools/hvdlint/thread_ownership.py plus the three true
# positives this analyzer found (and this PR fixed) in the tree.

@pytest.fixture(scope="module")
def mut_tree(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("mut") / "horovod_tpu")
    shutil.copytree(os.path.join(REPO, "horovod_tpu"), dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def _mutate_and_lint(tree, rel, transform, analyzer):
    full = os.path.join(tree, rel)
    with open(full) as f:
        orig = f.read()
    mutated = transform(orig)
    assert mutated != orig, f"mutation anchor vanished in {rel}"
    with open(full, "w") as f:
        f.write(mutated)
    try:
        return lint_paths([tree], [analyzer])
    finally:
        with open(full, "w") as f:
            f.write(orig)


def test_mutation_on_arrivals_double_read_refound(mut_tree):
    """Historical bug #1: the _on_arrivals hook read twice while
    attach_trace can rebind it between the reads."""
    def revert(s):
        old = ("        on_arrivals = self._on_arrivals\n"
               "        track = (expect_tag == TAG_REQUESTS\n"
               "                 and on_arrivals is not None)")
        assert old in s
        s = s.replace(old,
                      "        track = (expect_tag == TAG_REQUESTS\n"
                      "                 and self._on_arrivals "
                      "is not None)", 1)
        return s.replace("on_arrivals(arrivals)",
                         "self._on_arrivals(arrivals)")
    fs = _mutate_and_lint(mut_tree, "common/controller.py", revert,
                          "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "controller.Controller._on_arrivals" in msgs \
        and "capture it into a local once" in msgs, fs


def test_mutation_mark_done_order_swap_refound(mut_tree):
    """Historical bug #2: mark_done publishing the status gate before
    the output payload that lock-free wait() keys on."""
    def swap(s):
        old = ("            self._outputs[handle] = output\n"
               "            self._results[handle] = status")
        assert old in s
        return s.replace(
            old,
            "            self._results[handle] = status\n"
            "            self._outputs[handle] = output", 1)
    fs = _mutate_and_lint(mut_tree, "common/tensor_table.py", swap,
                          "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "tensor_table.HandleManager._results" in msgs \
        and "before storing payload" in msgs, fs


def test_mutation_bucket_sets_in_place_refound(mut_tree):
    """Historical bug #3: note_bucket_names mutating the set in place
    instead of snapshot-swapping a fresh frozenset."""
    def aug(s):
        old = "        self._bucket_sets = cur | {s}"
        assert old in s
        return s.replace(old, "        self._bucket_sets |= {s}", 1)
    fs = _mutate_and_lint(mut_tree, "common/runtime.py", aug,
                          "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "runtime.Runtime._bucket_sets" in msgs, fs


def test_mutation_coordinator_pragma_strip_refound(mut_tree):
    """The ResponseCache audit is load-bearing: stripping the owned-by
    pragmas must re-flag the fields, proving the clean tree is clean
    because of reviewed justifications, not analyzer blindness."""
    def strip(s):
        return "".join(ln for ln in s.splitlines(True)
                       if "hvdlint: owned-by" not in ln)
    fs = _mutate_and_lint(mut_tree, "common/coordinator.py", strip,
                          "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "coordinator.ResponseCache" in msgs, fs


def test_mutation_native_inline_temp_refound(mut_tree):
    """native-lifetime real-tree gate: inlining pack()'s output buffer
    into the call expression must be re-found."""
    def inline(s):
        assert "out.ctypes.data_as" in s
        return s.replace("out.ctypes.data_as",
                         "np.empty(total, dtype).ctypes.data_as", 1)
    fs = _mutate_and_lint(mut_tree, "native.py", inline,
                          "native-lifetime")
    msgs = "\n".join(f.message for f in fs)
    assert "unnamed temporary" in msgs, fs


def test_mutation_steady_generation_strip_refound(mut_tree):
    """native-lifetime real-tree gate: dropping the arena generation
    from steady's iovec cache keys must be re-found (ensure()
    reallocates on growth; a stale pointer bundle writes freed
    memory)."""
    def strip(s):
        assert s.count("scratch.generation") >= 2
        return s.replace("scratch.generation", "0")
    fs = _mutate_and_lint(mut_tree, "common/steady.py", strip,
                          "native-lifetime")
    msgs = "\n".join(f.message for f in fs)
    assert "generation" in msgs, fs


def test_regression_stall_inspector_warned_lock(mut_tree):
    """True positive #1 fixed by this analyzer: StallInspector._warned
    was mutated from the caller thread with no lock while the
    background sweep also writes it. Reverting the lock re-fires."""
    def unlock(s):
        old = ("        with self._warned_lock:\n"
               "            self._warned.discard(name)")
        assert old in s
        return s.replace(old, "        self._warned.discard(name)", 1)
    fs = _mutate_and_lint(mut_tree, "common/coordinator.py", unlock,
                          "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "coordinator.StallInspector._warned" in msgs, fs


def test_regression_socket_ops_hook_capture(mut_tree):
    """True positive #2: the ring's metric hook was tested then used
    (two reads) while attach_metrics can rebind it between them."""
    def revert(s):
        old = ("            m_link = self._m_ring_link_bytes\n"
               "            if self._ring is not None and m_link "
               "is not None:\n"
               "                self._ring.m_link_bytes = m_link")
        assert old in s
        return s.replace(
            old,
            "            if self._ring is not None and \\\n"
            "                    self._m_ring_link_bytes "
            "is not None:\n"
            "                self._ring.m_link_bytes = "
            "self._m_ring_link_bytes", 1)
    fs = _mutate_and_lint(mut_tree, "ops/socket_ops.py", revert,
                          "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "socket_ops.SocketBackend._m_ring_link_bytes" in msgs, fs


def test_regression_tenant_lane_handoff_lock(mut_tree):
    """True positive #3: teardown handed _tenant_lane off with no lock
    while the scheduler's attach path rebinds it from its own
    thread. Reverting the lane lock re-fires."""
    def unlock(s):
        old = ("        with self._lane_lock:\n"
               "            lane, self._tenant_lane = "
               "self._tenant_lane, None\n"
               "            self._lane_closed = True")
        assert old in s
        return s.replace(
            old,
            "        lane, self._tenant_lane = "
            "self._tenant_lane, None\n"
            "        self._lane_closed = True", 1)
    fs = _mutate_and_lint(mut_tree, "common/runtime.py", unlock,
                          "thread-ownership")
    msgs = "\n".join(f.message for f in fs)
    assert "runtime.Runtime._tenant_lane" in msgs, fs


# -- jax_compat -------------------------------------------------------------
# Three checks, each with a known-bad fixture that must fire and a
# known-good twin that must stay silent, plus real-tree mutation gates
# reverting the shim-ported idiom (the exact rot that kept the 52-test
# shard_map family red from PR 3 to PR 20).

def test_jax_compat_floor_mirrors_shim():
    """The analyzer may not import the package under analysis, so it
    carries the supported-jax floor as a literal — this is the bolt
    keeping the two declarations (and the pyproject pin) one value."""
    from tools.hvdlint import jax_compat
    from horovod_tpu.compat import jaxshim
    assert jax_compat.SUPPORTED_FLOOR == jaxshim.SUPPORTED_JAX_FLOOR


# check 1: version-ranged API table — removed symbols...
BAD_JAX_REMOVED_API = """
    from jax.experimental.maps import Mesh

    def build(devs):
        return Mesh(devs, ("data",))
"""

# ...function-scoped imports (the tree's dominant jax idiom) count too
BAD_JAX_DEFERRED_TREE_MAP = """
    def halve(tree):
        import jax
        return jax.tree_map(lambda x: x / 2, tree)
"""

# ...and symbols introduced ABOVE the supported floor are rot as well
BAD_JAX_ABOVE_FLOOR = """
    import jax

    def size(axis):
        return jax.lax.axis_size(axis)
"""

GOOD_JAX_VIA_SHIM = """
    from horovod_tpu.compat import jaxshim

    def run(f, devs):
        mesh = jaxshim.make_mesh({"data": 4}, devices=devs)
        spec = jaxshim.partition_spec("data")
        return jaxshim.shard_map(f, mesh=mesh, in_specs=spec,
                                 out_specs=spec)
"""


def test_jax_compat_removed_api_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_JAX_REMOVED_API, "jax_compat")
    msgs = "\n".join(f.message for f in fs)
    assert "jax.experimental.maps" in msgs and "removed" in msgs, fs


def test_jax_compat_deferred_import_fires(tmp_path):
    """jax.tree_map reached through a function-body import: the
    analyzer's whole-file import overlay must still resolve it."""
    fs = _lint_snippet(tmp_path, BAD_JAX_DEFERRED_TREE_MAP,
                       "jax_compat")
    msgs = "\n".join(f.message for f in fs)
    assert "jax.tree_map" in msgs \
        and "jax.tree_util.tree_map" in msgs, fs


def test_jax_compat_above_floor_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_JAX_ABOVE_FLOOR, "jax_compat")
    msgs = "\n".join(f.message for f in fs)
    assert "jax.lax.axis_size" in msgs \
        and "above the supported floor" in msgs \
        and "jaxshim.axis_size" in msgs, fs


def test_jax_compat_shim_usage_is_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_JAX_VIA_SHIM,
                         "jax_compat") == []


# check 2: mesh/sharding construction must route through the shim
BAD_DIRECT_CONSTRUCTION = """
    def build(devs):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(devs, ("data",))
        return NamedSharding(mesh, PartitionSpec("data"))
"""

GOOD_SHIM_CONSTRUCTION = """
    from horovod_tpu.compat import jaxshim

    def build(devs):
        mesh = jaxshim.make_mesh({"data": 2, "model": 2},
                                 devices=devs)
        spec = jaxshim.partition_spec("data", "model")
        return jaxshim.named_sharding(mesh, spec)
"""


def test_jax_compat_direct_construction_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_DIRECT_CONSTRUCTION, "jax_compat")
    msgs = "\n".join(f.message for f in fs)
    assert "direct jax.sharding.Mesh construction" in msgs \
        and "make_mesh" in msgs, fs
    assert "direct jax.sharding.NamedSharding construction" in msgs \
        and "named_sharding" in msgs, fs


def test_jax_compat_shim_construction_is_clean(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_SHIM_CONSTRUCTION,
                         "jax_compat") == []


def test_jax_compat_shim_module_itself_exempt(tmp_path):
    """The one sanctioned call site: a module named jaxshim.py may
    touch the version-ranged API directly — that's its whole job."""
    code = """
        import jax

        def make_raw_mesh(devs, names):
            return jax.sharding.Mesh(devs, names)
    """
    assert _lint_snippet(tmp_path, code, "jax_compat",
                         name="jaxshim.py") == []


# check 3: PartitionSpec axis names must be axes of a mesh in scope
BAD_STALE_AXIS = """
    from horovod_tpu.compat import jaxshim

    def build(devs):
        mesh = jaxshim.make_mesh({"data": 2, "model": 2},
                                 devices=devs)
        return jaxshim.named_sharding(
            mesh, jaxshim.partition_spec("data", "modle"))
"""

GOOD_UNPROVABLE_MESH_SKIPPED = """
    from horovod_tpu.compat import jaxshim

    def apply(mesh):
        # mesh arrives as a parameter: axes statically unknown, so
        # the check must skip rather than guess
        return jaxshim.partition_spec("whatever")
"""


def test_jax_compat_stale_axis_fires(tmp_path):
    fs = _lint_snippet(tmp_path, BAD_STALE_AXIS, "jax_compat")
    msgs = "\n".join(f.message for f in fs)
    assert "'modle'" in msgs and "silently replicates" in msgs, fs
    assert "'data'" not in msgs.split("known axes")[0], \
        "the coherent axis must not be flagged"


def test_jax_compat_unprovable_mesh_is_skipped(tmp_path):
    assert _lint_snippet(tmp_path, GOOD_UNPROVABLE_MESH_SKIPPED,
                         "jax_compat") == []


# real-tree gates: reverting a shim-ported file to the removed-API
# idiom must trip jax_compat on the actual package, proving the green
# tree is green because the port is complete, not because the
# analyzer is blind to the shipped code.

def test_mutation_axis_size_revert_refound(mut_tree):
    """spmd.axis_size reverted to the above-floor jax.lax.axis_size
    spelling (the exact AttributeError that killed the family on
    0.4.37)."""
    def revert(s):
        old = "    return jaxshim.axis_size(axis)"
        assert old in s
        return s.replace(old, "    return jax.lax.axis_size(axis)", 1)
    fs = _mutate_and_lint(mut_tree, "spmd/__init__.py", revert,
                          "jax_compat")
    msgs = "\n".join(f.message for f in fs)
    assert "jax.lax.axis_size" in msgs \
        and "above the supported floor" in msgs, fs


def test_mutation_shard_map_revert_refound(mut_tree):
    """ring_attention's shard_map reverted to the top-level jax
    spelling that only exists from 0.5.0."""
    def revert(s):
        old = "partial(jaxshim.shard_map, mesh=mesh"
        assert old in s
        return s.replace(old, "partial(jax.shard_map, mesh=mesh", 1)
    fs = _mutate_and_lint(mut_tree, "parallel/ring_attention.py",
                          revert, "jax_compat")
    msgs = "\n".join(f.message for f in fs)
    assert "jax.shard_map" in msgs \
        and "compat.jaxshim.shard_map" in msgs, fs


def test_mutation_direct_sharding_revert_refound(mut_tree):
    """spmd's named_sharding helper reverted to constructing
    jax.sharding.NamedSharding directly."""
    def revert(s):
        old = ("    return jaxshim.named_sharding("
               "mesh, jaxshim.partition_spec(axis))")
        assert old in s
        return s.replace(
            old,
            "    return jax.sharding.NamedSharding("
            "mesh, jaxshim.partition_spec(axis))", 1)
    fs = _mutate_and_lint(mut_tree, "spmd/__init__.py", revert,
                          "jax_compat")
    msgs = "\n".join(f.message for f in fs)
    assert "direct jax.sharding.NamedSharding construction" in msgs, fs


# -- the --changed cache ----------------------------------------------------

def _seed_pkg(tmp_path):
    pkg = tmp_path / "cpkg"
    pkg.mkdir()
    (pkg / "a.py").write_text(
        "import os\nX = os.environ.get('HOROVOD_CACHE_PROBE')\n")
    (pkg / "b.py").write_text("Y = 1\n")
    return pkg


def test_cache_replays_when_nothing_changed(tmp_path):
    from tools.hvdlint import cache as hcache
    pkg = _seed_pkg(tmp_path)
    cf = str(tmp_path / "c.json")
    findings = lint_paths([str(pkg)], ["knobs"])
    assert findings, "seed must produce a finding"
    hcache.save([str(pkg)], ["knobs"], cf, findings)
    replay = hcache.load([str(pkg)], ["knobs"], cf)
    assert replay is not None
    assert [f.to_dict() for f in replay] == \
        [f.to_dict() for f in findings]


def test_cache_survives_mtime_touch(tmp_path):
    from tools.hvdlint import cache as hcache
    pkg = _seed_pkg(tmp_path)
    cf = str(tmp_path / "c.json")
    hcache.save([str(pkg)], ["knobs"], cf,
                lint_paths([str(pkg)], ["knobs"]))
    # mtime bump, identical content: sha1 fallback must still replay
    a = pkg / "a.py"
    os.utime(a, (os.path.getmtime(a) + 10,) * 2)
    assert hcache.load([str(pkg)], ["knobs"], cf) is not None


def test_cache_invalidated_by_edit(tmp_path):
    from tools.hvdlint import cache as hcache
    pkg = _seed_pkg(tmp_path)
    cf = str(tmp_path / "c.json")
    hcache.save([str(pkg)], ["knobs"], cf,
                lint_paths([str(pkg)], ["knobs"]))
    (pkg / "b.py").write_text("Y = 2\n")
    assert hcache.load([str(pkg)], ["knobs"], cf) is None


def test_cache_invalidated_by_rename(tmp_path):
    from tools.hvdlint import cache as hcache
    pkg = _seed_pkg(tmp_path)
    cf = str(tmp_path / "c.json")
    hcache.save([str(pkg)], ["knobs"], cf,
                lint_paths([str(pkg)], ["knobs"]))
    os.rename(pkg / "b.py", pkg / "b2.py")
    assert hcache.load([str(pkg)], ["knobs"], cf) is None


def test_cache_invalidated_by_pragma_change(tmp_path):
    """A pragma edit changes no code object but DOES change findings —
    it must invalidate like any other content change."""
    from tools.hvdlint import cache as hcache
    pkg = _seed_pkg(tmp_path)
    cf = str(tmp_path / "c.json")
    hcache.save([str(pkg)], ["knobs"], cf,
                lint_paths([str(pkg)], ["knobs"]))
    a = pkg / "a.py"
    a.write_text(a.read_text() + "# hvdlint: disable=knobs -- probe\n")
    assert hcache.load([str(pkg)], ["knobs"], cf) is None


def test_cache_invalidated_by_analyzer_selection(tmp_path):
    from tools.hvdlint import cache as hcache
    pkg = _seed_pkg(tmp_path)
    cf = str(tmp_path / "c.json")
    hcache.save([str(pkg)], ["knobs"], cf,
                lint_paths([str(pkg)], ["knobs"]))
    assert hcache.load([str(pkg)], ["knobs", "teardown"], cf) is None


def test_cache_invalidated_by_api_table_edit(tmp_path):
    """jax_compat's API_TABLE is data, but it IS the analyzer: adding
    a row must change the tool stamp (so a --changed replay re-runs),
    and load() must key on that stamp."""
    from tools.hvdlint import cache as hcache
    scratch = str(tmp_path / "hvdlint")
    shutil.copytree(os.path.join(REPO, "tools", "hvdlint"), scratch,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = hcache._tool_stamp(scratch)
    assert before == hcache._tool_stamp(), \
        "scratch copy must stamp identically to the shipped suite"
    jc = os.path.join(scratch, "jax_compat.py")
    with open(jc) as f:
        src = f.read()
    anchor = "API_TABLE: Dict[str, Tuple[Optional[tuple], " \
             "Optional[tuple], str]] = {"
    assert anchor in src
    with open(jc, "w") as f:
        f.write(src.replace(
            anchor,
            anchor + '\n    "jax.experimental.probe": '
                     '(None, (0, 9, 0), "nothing"),', 1))
    after = hcache._tool_stamp(scratch)
    assert after != before, "API-table edit must change the tool stamp"

    # and the load path enforces it: a cache saved under another
    # suite build is a miss, never a replay
    pkg = _seed_pkg(tmp_path)
    cf = str(tmp_path / "c.json")
    hcache.save([str(pkg)], ["knobs"], cf,
                lint_paths([str(pkg)], ["knobs"]))
    assert hcache.load([str(pkg)], ["knobs"], cf) is not None
    with open(cf) as f:
        payload = json.load(f)
    payload["tool"] = after
    with open(cf, "w") as f:
        json.dump(payload, f)
    assert hcache.load([str(pkg)], ["knobs"], cf) is None


def test_cache_cli_end_to_end(tmp_path):
    pkg = _seed_pkg(tmp_path)
    cf = str(tmp_path / "cli.json")
    cmd = [sys.executable, "-m", "tools.hvdlint", str(pkg),
           "--changed", "--cache-file", cf]
    first = subprocess.run(cmd, cwd=REPO, capture_output=True,
                           text=True, timeout=120)
    assert first.returncode == 1 and os.path.exists(cf)
    second = subprocess.run(cmd, cwd=REPO, capture_output=True,
                            text=True, timeout=120)
    assert second.returncode == 1
    assert second.stdout == first.stdout


# -- flight-recorder hygiene ------------------------------------------------

def test_no_stray_flight_dumps_at_repo_root():
    """In-process aborts used to dump hvd-flight-*.jsonl into the CWD
    (the checkout, under pytest). tests/conftest.py now defaults
    HOROVOD_TPU_FLIGHT_DIR to a throwaway dir; a stray file here means
    some path bypassed it."""
    strays = glob.glob(os.path.join(REPO, "hvd-flight-*.jsonl"))
    assert strays == [], strays
