"""Unit tests for the overlap tier (common/overlap.py, the chunked
native transfer in common/steady.py, the autotuned bucket count) plus
the satellite regressions that ride this PR (aggregate-frame
truncation, IPv6 loopback leaf filtering, int32-offset guard in the
skewed-allgather psum path)."""

import socket
import threading
import time

import numpy as np
import pytest

from horovod_tpu.common import overlap as hoverlap
from horovod_tpu.common.controller import (
    _dialable_leaf_ip, pack_frames, unpack_frames,
)


# -- bucket planner ------------------------------------------------------
def test_plan_buckets_balanced_and_contiguous():
    sizes = [100] * 8
    ends = hoverlap.plan_buckets(sizes, 4, 0)
    assert ends == [2, 4, 6, 8]


def test_plan_buckets_derives_count_from_bytes():
    sizes = [1000] * 10
    ends = hoverlap.plan_buckets(sizes, 0, 2500)  # 10000/2500 = 4
    assert ends is not None and ends[-1] == 10 and len(ends) == 4


def test_plan_buckets_off_and_degenerate():
    assert hoverlap.plan_buckets([100] * 8, 0, 0) is None
    assert hoverlap.plan_buckets([100], 4, 0) is None
    assert hoverlap.plan_buckets([], 4, 0) is None
    assert hoverlap.plan_buckets([0, 0], 4, 0) is None


def test_plan_buckets_clamps_to_tensor_count_and_cap():
    ends = hoverlap.plan_buckets([10, 10, 10], 8, 0)
    assert ends is not None and len(ends) <= 3 and ends[-1] == 3
    ends = hoverlap.plan_buckets([10] * 64, 64, 0)
    assert len(ends) == hoverlap.MAX_BUCKETS


def test_plan_buckets_skewed_sizes_stay_nonempty():
    sizes = [10_000_000, 1, 1, 1]
    ends = hoverlap.plan_buckets(sizes, 4, 0)
    assert ends[-1] == 4
    last = 0
    for e in ends:
        assert e > last  # every bucket non-empty, boundaries ascend
        last = e


def test_plan_buckets_pure_function():
    sizes = [3, 1, 4, 1, 5, 9, 2, 6]
    assert hoverlap.plan_buckets(sizes, 3, 0) \
        == hoverlap.plan_buckets(list(sizes), 3, 0)


# -- overlap runner ------------------------------------------------------
def _mk_cycle(seq, plan=None):
    return hoverlap.InflightCycle(plan or object(), [], [], [], seq)


def test_runner_fifo_order_and_done_flow():
    order = []

    def run_fn(plan, bufs):
        order.append(plan)
        return ("done", plan)

    r = hoverlap.OverlapRunner(run_fn, max_inflight=2)
    try:
        plans = [object() for _ in range(4)]
        for i, p in enumerate(plans):
            r.submit(_mk_cycle(i, p))
        got = []
        deadline = time.monotonic() + 5
        while len(got) < 4 and time.monotonic() < deadline:
            c = r.wait_completed(0.5)
            if c is not None:
                got.append(c)
        assert [c.plan for c in got] == plans  # strict FIFO
        assert order == plans
        assert all(c.outcome[0] == "done" for c in got)
        assert r.cycles_total == 4
    finally:
        r.stop()


def test_runner_deviation_stalls_and_cancel_resumes():
    def run_fn(plan, bufs):
        if plan == "bad":
            return ("frame", b"classic")
        return ("done", plan)

    r = hoverlap.OverlapRunner(run_fn, max_inflight=4)
    try:
        r.submit(_mk_cycle(0, "bad"))
        c = r.wait_completed(5.0)
        assert c is not None and c.outcome == ("frame", b"classic")
        assert r.stalled
        # stalled runner refuses new work until the bg loop resolves
        with pytest.raises(RuntimeError):
            r.submit(_mk_cycle(1, "later"))
        assert r.cancel_pending() == []
        assert not r.stalled
        r.submit(_mk_cycle(2, "ok"))
        c = r.wait_completed(5.0)
        assert c is not None and c.outcome == ("done", "ok")
    finally:
        r.stop()


def test_runner_parks_exception_for_drain():
    def run_fn(plan, bufs):
        raise ConnectionError("wire died")

    r = hoverlap.OverlapRunner(run_fn, max_inflight=2)
    try:
        r.submit(_mk_cycle(0))
        c = r.wait_completed(5.0)
        assert c is not None
        kind, err = c.outcome
        assert kind == "error" and isinstance(err, ConnectionError)
        assert r.stalled
    finally:
        r.stop()


def test_runner_same_plan_exclusion():
    """A plan whose arena views are on the wire must not be repacked:
    submit blocks until the first cycle of the same plan is DRAINED."""
    release = threading.Event()

    def run_fn(plan, bufs):
        release.wait(5.0)
        return ("done", None)

    r = hoverlap.OverlapRunner(run_fn, max_inflight=4)
    try:
        plan = object()
        r.submit(_mk_cycle(0, plan))
        blocked = threading.Event()
        submitted = threading.Event()

        def second():
            blocked.set()
            r.submit(_mk_cycle(1, plan))
            submitted.set()

        t = threading.Thread(target=second, daemon=True)
        t.start()
        blocked.wait(5.0)
        assert not submitted.wait(0.3)  # still excluded
        release.set()
        c = r.wait_completed(5.0)   # drain the first cycle
        assert c is not None
        assert submitted.wait(5.0)  # now the second went through
        c = r.wait_completed(5.0)
        assert c is not None
        t.join(5.0)
    finally:
        r.stop()


def test_runner_stop_returns_leftovers():
    hold = threading.Event()

    def run_fn(plan, bufs):
        hold.wait(0.5)
        return ("done", None)

    r = hoverlap.OverlapRunner(run_fn, max_inflight=4)
    r.submit(_mk_cycle(0, "a"))
    r.submit(_mk_cycle(1, "b"))
    r.submit(_mk_cycle(2, "c"))
    hold.set()
    leftovers = r.stop()
    # everything undrained comes back (pending and/or completed)
    assert len(leftovers) == 3


# -- tuned trailer + overlap tuner ---------------------------------------
def test_response_list_trailer_roundtrip():
    from horovod_tpu.common import wire
    from horovod_tpu.common.message import ResponseList

    rl = ResponseList([], shutdown=False, tuned_cycle_time_ms=3.5,
                      tuned_fusion_threshold_bytes=1 << 20,
                      tuned_overlap_buckets=4)
    out = wire.parse_response_list(wire.serialize_response_list(rl))
    assert out.tuned_overlap_buckets == 4
    assert out == rl
    rl2 = ResponseList([])
    out2 = wire.parse_response_list(wire.serialize_response_list(rl2))
    assert out2.tuned_overlap_buckets == -1  # no-verdict sentinel


def test_overlap_tuner_settles_argmax():
    from horovod_tpu.common.parameter_manager import _OverlapTuner

    t = _OverlapTuner([0, 2, 4])
    score = {0: 1.0, 2: 5.0, 4: 3.0}
    while not t.done:
        t.feed(score[t.current()], traffic=100)
    assert t.choice == 2


def test_overlap_tuner_ignores_lulls():
    from horovod_tpu.common.parameter_manager import _OverlapTuner

    t = _OverlapTuner([0, 2])
    cur = t.current()
    t.feed(9.0, traffic=0)  # global lull: not a measurement
    assert t.current() == cur and not t.done


def test_parameter_manager_overlap_gating():
    """The overlap grid only measures after the wire sweep settles,
    workers adopt the trailer value, and spec stays safe while the
    overlap grid runs."""
    from horovod_tpu.common.config import Config
    from horovod_tpu.common.parameter_manager import ParameterManager

    class _Ctl:
        rank = 0

    cfg = Config()
    cfg.autotune = True
    pm = ParameterManager(cfg, _Ctl())
    pm.configure_overlap(True)
    assert pm.overlap_buckets() in (0, 2, 4, 8)
    assert pm.spec_safe  # overlap grid needs live speculation
    assert pm.tuned_overlap_buckets >= 0

    class _Ctl1:
        rank = 1

    worker = ParameterManager(cfg, _Ctl1())
    assert worker.overlap_buckets() is None
    worker.apply_synced(1 << 20, 2.0, overlap_buckets=4)
    assert worker.overlap_buckets() == 4
    worker.apply_synced(1 << 20, 2.0, overlap_buckets=-1)
    assert worker.overlap_buckets() == 4  # sentinel never clears


# -- chunked pipelined transfer ------------------------------------------
def _native_lib():
    from horovod_tpu import native as _nat
    lib = _nat.get()
    if lib is None or not hasattr(lib, "hvd_steady_worker_chunked"):
        pytest.skip("native core unavailable")
    return lib


def test_steady_plan_defers_cast_when_chunked():
    from horovod_tpu.common import wire_dtype as _wd
    from horovod_tpu.common.arena import FusionArena
    from horovod_tpu.common.message import DataType
    from horovod_tpu.common.steady import SteadyPlan

    _native_lib()
    n = 64
    segments = [(_wd.wire_datatype(_wd.WIRE_BF16),
                 _wd.wire_np_dtype(_wd.WIRE_BF16), n * 2, np.float32)]
    plan = SteadyPlan(1, 64, 0b1, segments, FusionArena(),
                      chunk_bytes=32)
    assert plan.chunked
    arrays = [np.linspace(-3, 3, n, dtype=np.float32)]
    plan.send_views[0].view(np.uint8)[:] = 0xEE  # sentinel
    bufs = plan.pack([arrays], [1.0])
    # the cast was DEFERRED: staging filled, wire view untouched
    np.testing.assert_array_equal(plan.stage_views[0], arrays[0])
    assert (plan.send_views[0].view(np.uint8) == 0xEE).all()
    # materialize_wire produces exactly the direct-cast bytes
    plan.materialize_wire()
    expect = np.empty(n, _wd.wire_np_dtype(_wd.WIRE_BF16))
    _wd.cast_into(arrays[0], expect)
    np.testing.assert_array_equal(
        plan.send_views[0].view(np.uint8), expect.view(np.uint8))
    assert bufs[0] is plan.send_views[0]


def test_steady_plan_chunk_gate_rejects_unsupported_cast_pairs():
    """hvd_cast only speaks f32<->bf16/f16: a float64-source
    compressed segment must NOT arm the chunked worker (the chunk
    loop would -EINVAL mid-frame and abort a healthy world) — it
    keeps the Python cast + classic one-shot send instead."""
    from horovod_tpu.common import wire_dtype as _wd
    from horovod_tpu.common.arena import FusionArena
    from horovod_tpu.common.steady import SteadyPlan

    _native_lib()
    n = 32
    f64_seg = [(_wd.wire_datatype(_wd.WIRE_BF16),
                _wd.wire_np_dtype(_wd.WIRE_BF16), n * 2, np.float64)]
    plan = SteadyPlan(1, 64, 0b1, f64_seg, FusionArena(),
                      chunk_bytes=64)
    assert not plan.chunked
    # ...and pack still produces correct wire bytes via the fallback
    arrays = [np.linspace(-1, 1, n, dtype=np.float64)]
    bufs = plan.pack([arrays], [1.0])
    expect = np.empty(n, _wd.wire_np_dtype(_wd.WIRE_BF16))
    _wd.cast_into(arrays[0], expect)
    np.testing.assert_array_equal(
        bufs[0].view(np.uint8), expect.view(np.uint8))
    # the supported pair still arms
    f32_seg = [(_wd.wire_datatype(_wd.WIRE_BF16),
                _wd.wire_np_dtype(_wd.WIRE_BF16), n * 2, np.float32)]
    assert SteadyPlan(1, 64, 0b1, f32_seg, FusionArena(),
                      chunk_bytes=64).chunked


@pytest.mark.parametrize("secret", [b"", b"shared-key"])
def test_chunked_worker_wire_parity(secret):
    """hvd_steady_worker_chunked must put byte-identical frames on
    the wire (chunking only reschedules the cast): capture its
    request frame over a socketpair and compare against the classic
    serialized frame; reply with a valid response so the cycle
    completes DONE."""
    import ctypes

    from horovod_tpu.common import steady as hsteady
    from horovod_tpu.common import wire_dtype as _wd
    from horovod_tpu.common.arena import FusionArena
    from horovod_tpu.common.message import DataType
    from horovod_tpu.common.steady import SteadyPlan

    lib = _native_lib()
    n = 256
    segments = [
        (_wd.wire_datatype(_wd.WIRE_BF16),
         _wd.wire_np_dtype(_wd.WIRE_BF16), n * 2, np.float32),
        (DataType.FLOAT32, np.float32, n * 4, None),
    ]
    plan = SteadyPlan(7, 64, 0b11, segments, FusionArena(),
                      chunk_bytes=100)  # forces several chunks
    assert plan.chunked
    comp = np.linspace(-2, 2, n, dtype=np.float32)
    raw = np.linspace(5, 6, n, dtype=np.float32)
    plan.pack([[comp], [raw]], [1.0, 1.0])

    # classic bytes: clone plan without chunking, same data
    ref = SteadyPlan(7, 64, 0b11, segments, FusionArena())
    ref_bufs = ref.pack([[comp], [raw]], [1.0, 1.0])
    classic = ref.frame_bytes(ref_bufs)

    a, b = socket.socketpair()
    captured = {}

    def peer():
        want = 5 + (32 if secret else 0) + plan.payload_nbytes
        buf = b""
        while len(buf) < want:
            chunk = b.recv(want - len(buf))
            if not chunk:
                break
            buf += chunk
        captured["frame"] = buf
        payload = buf[5 + (32 if secret else 0):]
        # echo a valid response frame (tag 3) with the same payload
        hdr = len(payload).to_bytes(4, "little") + bytes([3])
        out = hdr
        if secret:
            import hashlib
            import hmac as _hmac
            out += _hmac.new(secret, bytes([3]) + payload,
                             hashlib.sha256).digest()
        b.sendall(out + payload)

    t = threading.Thread(target=peer, daemon=True)
    t.start()
    kind, val = hsteady.run_worker_cycle(
        lib, plan, a.fileno(), secret, b"", 2, 3, (5.0, 0.1))
    t.join(5.0)
    a.close()
    b.close()
    assert kind == hsteady.DONE, (kind, val)
    payload = captured["frame"][5 + (32 if secret else 0):]
    assert payload == classic  # byte-identical wire format
    # the echoed "world result" round-trips into typed segments
    (dt0, seg0), (dt1, seg1) = val
    np.testing.assert_array_equal(
        seg0.view(np.uint8), ref_bufs[0].view(np.uint8))
    np.testing.assert_array_equal(seg1, ref_bufs[1])


# -- satellite regressions ----------------------------------------------
def test_unpack_frames_truncation_raises_connection_error():
    """Every prefix cut of a packed aggregate must raise
    ConnectionError — never a raw struct.error escaping the relay
    error handling (ADVICE r05)."""
    blob = pack_frames([b"alpha", b"", b"gamma" * 7])
    assert unpack_frames(blob) == [b"alpha", b"", b"gamma" * 7]
    for cut in range(len(blob)):
        with pytest.raises(ConnectionError):
            unpack_frames(blob[:cut])
    with pytest.raises(ConnectionError):
        unpack_frames(blob + b"x")  # trailing garbage too


def test_dialable_leaf_ip_loopback_families():
    assert not _dialable_leaf_ip("127.0.0.1")
    assert not _dialable_leaf_ip("127.8.9.10")
    assert not _dialable_leaf_ip("::1")  # IPv6 loopback (ADVICE r05)
    assert _dialable_leaf_ip("10.0.0.5")
    assert _dialable_leaf_ip("fe80::1")
    assert not _dialable_leaf_ip("not-an-ip")


def test_ragged_psum_guard_int32_boundary():
    """ >= 2^31 assembled psum elements must route to the padded
    path: a 32-bit offset would silently wrap (ADVICE r05). At the
    boundary the skew is extreme, so without the guard psum wins."""
    from horovod_tpu.ops.xla_ops import ragged_psum_wins

    ws = 8
    # Small case with the same skew shape: psum wins (sanity).
    small = [1000] + [1] * (ws - 1)
    assert ragged_psum_wins(small, [1], ws)
    # Scale rows so psum_elems = sum(rows) + max crosses 2^31.
    big = 2**30
    rows = [big] + [1] * (ws - 1)
    assert ragged_psum_wins(rows, [1], ws) is False
    # Just under the boundary with identical skew: still allowed.
    under = [2**29] + [1] * (ws - 1)
    assert ragged_psum_wins(under, [1], ws) is True
