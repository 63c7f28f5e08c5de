"""Unit tests for the steady-state negotiation fast path: the
world-coherent ResponseCache (slot assignment, LRU eviction,
invalidation), the cache-coherence wire frames, and the runtime's
unfuse/replay helpers. Cross-rank coherence is modeled by feeding two
cache instances the SAME world-identical event stream with DIFFERENT
rank-local signatures (device ids, allgather dim-0) and asserting their
coherent state fingerprints stay bit-identical — the invariant the
bitmask protocol stands on. End-to-end multi-process coverage lives in
tests/test_multiprocess.py (response_cache_* and cache_byte_budget)."""

import pytest

from horovod_tpu.common import wire
from horovod_tpu.common.coordinator import ResponseCache, fuse_responses
from horovod_tpu.common.message import (
    CacheCycleRequest, CacheCycleResponse, DataType, Request, RequestList,
    RequestType, Response, ResponseList, ResponseType,
    numpy_dtype_to_datatype,
)


def _req(name, rank=0, shape=(4,), dtype=DataType.FLOAT32, device=-1,
         op=RequestType.ALLREDUCE, root=-1):
    return Request(request_rank=rank, request_type=op, tensor_type=dtype,
                   tensor_name=name, root_rank=root, device=device,
                   tensor_shape=shape)


def _resp(name, numel=4, devices=(-1, -1)):
    return Response(response_type=ResponseType.ALLREDUCE,
                    tensor_names=[name], devices=list(devices),
                    tensor_sizes=[numel])


def _put(cache, name, req=None, resp=None):
    req = req or _req(name)
    cache.put(name, ResponseCache.signature(req), resp or _resp(name),
              req.tensor_type, 1)


class TestResponseCache:
    def test_lookup_states(self):
        c = ResponseCache(4)
        assert c.lookup(_req("g"))[0] == ResponseCache.MISS
        _put(c, "g")
        state, slot = c.lookup(_req("g"))
        assert state == ResponseCache.HIT and slot == 0
        # shape change -> INVALID, same slot reported for eviction
        state, slot = c.lookup(_req("g", shape=(8,)))
        assert state == ResponseCache.INVALID and slot == 0
        # dtype change -> INVALID too
        state, _ = c.lookup(_req("g", dtype=DataType.FLOAT64))
        assert state == ResponseCache.INVALID
        assert c.hits == 1 and c.misses == 3

    def test_lru_capacity_eviction_and_slot_reuse(self):
        c = ResponseCache(2)
        _put(c, "a")
        _put(c, "b")
        _put(c, "c")  # evicts a (LRU), reuses its slot 0
        assert c.lookup(_req("a"))[0] == ResponseCache.MISS
        assert c.lookup(_req("c")) == (ResponseCache.HIT, 0)
        assert c.lookup(_req("b")) == (ResponseCache.HIT, 1)

    def test_touch_steers_eviction_order(self):
        c = ResponseCache(2)
        _put(c, "a")
        _put(c, "b")
        c.touch_mask(0b01)  # a is now most-recently-used
        _put(c, "c")        # so b gets evicted, not a
        assert c.lookup(_req("b"))[0] == ResponseCache.MISS
        assert c.lookup(_req("a"))[0] == ResponseCache.HIT

    def test_touch_does_not_bump_epoch(self):
        """Hit cycles must not invalidate steady-state replay plans:
        only structural mutations (puts/evictions) move the epoch."""
        c = ResponseCache(4)
        _put(c, "a")
        e = c.epoch
        c.touch_mask(0b1)
        assert c.epoch == e

    def test_evict_slots_mask_ascending(self):
        c = ResponseCache(4)
        for n in "abcd":
            _put(c, n)
        c.evict_slots(0b0101)  # slots 0 and 2 -> a and c
        assert c.lookup(_req("a"))[0] == ResponseCache.MISS
        assert c.lookup(_req("c"))[0] == ResponseCache.MISS
        assert c.lookup(_req("b"))[0] == ResponseCache.HIT
        # freed slots are reused lowest-first — deterministically
        _put(c, "e")
        assert c.lookup(_req("e")) == (ResponseCache.HIT, 0)

    def test_two_ranks_march_in_lockstep(self):
        """The coherence contract: identical event streams with
        DIFFERENT rank-local signatures (device ids, allgather dim-0)
        must leave the coherent state — slot map, LRU order, epoch —
        bit-identical. This is what lets a slot bit stand in for a
        serialized Request."""
        r0, r1 = ResponseCache(3), ResponseCache(3)
        names = ["g0", "g1", "g2", "g3", "g0", "g4"]
        for i, n in enumerate(names):
            resp = _resp(n)
            # rank 0 submits on device 0, rank 1 on device 1, and their
            # allgather-ish shapes differ — signatures are local-only
            r0.put(n, ResponseCache.signature(
                _req(n, rank=0, device=0, shape=(i + 1, 4))),
                resp, DataType.FLOAT32, 4)
            r1.put(n, ResponseCache.signature(
                _req(n, rank=1, device=1, shape=(2 * i + 1, 4))),
                resp, DataType.FLOAT32, 4)
            assert r0.state_fingerprint() == r1.state_fingerprint()
        # mask-driven events stay coherent too
        r0.touch_mask(0b011)
        r1.touch_mask(0b011)
        r0.evict_slots(0b010)
        r1.evict_slots(0b010)
        assert r0.state_fingerprint() == r1.state_fingerprint()


class TestCycleFrames:
    def test_full_request_round_trip(self):
        rl = RequestList([_req("a"), _req("b", rank=3)], shutdown=True)
        out = wire.parse_cycle_request(wire.serialize_cycle_request(rl))
        assert isinstance(out, RequestList) and out == rl

    def test_cached_request_round_trip(self):
        cf = CacheCycleRequest(epoch=42, nslots=19, hit_mask=0b1011,
                               invalid_mask=1 << 17,
                               requests=[_req("u", rank=2)],
                               shutdown=True)
        out = wire.parse_cycle_request(wire.serialize_cycle_request(cf))
        assert isinstance(out, CacheCycleRequest) and out == cf

    def test_cached_request_frame_is_capacity_bounded(self):
        """The steady-state frame is O(nslots/8) bytes — the whole
        point of the fast path (the byte-budget mp test asserts the
        live world's traffic; this pins the encoding itself)."""
        cf = CacheCycleRequest(epoch=1, nslots=1024,
                               hit_mask=(1 << 1024) - 1,
                               invalid_mask=0, requests=[])
        frame = wire.serialize_cycle_request(cf)
        assert len(frame) <= 2 * (1024 // 8) + 32, len(frame)

    def test_full_response_round_trip(self):
        rl = ResponseList([_resp("a")], shutdown=False,
                          tuned_cycle_time_ms=2.0,
                          tuned_fusion_threshold_bytes=4096)
        out = wire.parse_cycle_response(
            wire.serialize_cycle_response(rl))
        assert isinstance(out, ResponseList) and out == rl

    def test_cached_response_round_trip(self):
        cr = CacheCycleResponse(
            epoch=7, nslots=9, grant_mask=0b101, invalid_mask=0b10,
            response_list=ResponseList([_resp("n")], shutdown=True,
                                       tuned_cycle_time_ms=1.5,
                                       tuned_fusion_threshold_bytes=64))
        out = wire.parse_cycle_response(
            wire.serialize_cycle_response(cr))
        assert isinstance(out, CacheCycleResponse) and out == cr

    def test_combine_folds_masks_and_concats_requests(self):
        a = wire.serialize_cycle_request(CacheCycleRequest(
            epoch=5, nslots=8, hit_mask=0b0111, invalid_mask=0b1000,
            requests=[_req("x", rank=1)]))
        b = wire.serialize_cycle_request(CacheCycleRequest(
            epoch=5, nslots=8, hit_mask=0b1101, invalid_mask=0b0010,
            requests=[_req("y", rank=2)], shutdown=True))
        combined = wire.combine_cycle_requests([a, b])
        assert combined is not None
        assert combined[0] == wire.FRAME_CACHED_AGG
        out = wire.parse_cycle_request(combined)
        assert out.hit_mask == 0b0101       # AND
        assert out.invalid_mask == 0b1010   # OR
        assert out.shutdown is True         # OR
        assert [r.tensor_name for r in out.requests] == ["x", "y"]
        assert [r.request_rank for r in out.requests] == [1, 2]

    def test_combine_is_associative_through_agg_frames(self):
        """A root's CACHED_AGG output can itself be folded again
        upstream (deeper trees)."""
        frames = [wire.serialize_cycle_request(CacheCycleRequest(
            epoch=1, nslots=4, hit_mask=m, invalid_mask=0,
            requests=[])) for m in (0b1111, 0b1110, 0b1011)]
        once = wire.combine_cycle_requests(frames[:2])
        twice = wire.combine_cycle_requests([once, frames[2]])
        assert wire.parse_cycle_request(twice).hit_mask == 0b1010

    def test_spec_request_round_trip(self):
        import numpy as np
        seg = [(DataType.FLOAT64, np.arange(8, dtype=np.float64)),
               (DataType.FLOAT32, np.ones(3, dtype=np.float32))]
        cf = CacheCycleRequest(epoch=3, nslots=9, hit_mask=0b101,
                               spec_payload=seg)
        frame = wire.serialize_cycle_request(cf)
        assert frame[0] == wire.FRAME_CACHED_SPEC
        out = wire.parse_cycle_request(frame)
        assert isinstance(out, CacheCycleRequest)
        assert out.hit_mask == 0b101 and out.epoch == 3
        assert out.requests == [] and not out.shutdown
        (d0, b0), (d1, b1) = out.spec_payload
        assert d0 == DataType.FLOAT64 and d1 == DataType.FLOAT32
        np.testing.assert_array_equal(
            np.frombuffer(b0, np.float64), np.arange(8.0))
        np.testing.assert_array_equal(
            np.frombuffer(b1, np.float32), np.ones(3, np.float32))

    def test_spec_response_round_trip(self):
        import numpy as np
        seg = [(DataType.FLOAT64, np.full(4, 36.0))]
        cr = CacheCycleResponse(epoch=7, nslots=5, grant_mask=0b11,
                                spec_payload=seg)
        out = wire.parse_cycle_response(
            wire.serialize_cycle_response(cr))
        assert isinstance(out, CacheCycleResponse)
        assert out.grant_mask == 0b11 and out.epoch == 7
        assert out.response_list.responses == []
        np.testing.assert_array_equal(
            np.frombuffer(out.spec_payload[0][1], np.float64),
            np.full(4, 36.0))

    def test_combine_refuses_spec_frames(self):
        """A local root must never mask-fold frames carrying fused
        payloads — the coordinator reduces them (the relay forwards
        them per-rank instead)."""
        import numpy as np
        spec = wire.serialize_cycle_request(CacheCycleRequest(
            epoch=1, nslots=4, hit_mask=0b1,
            spec_payload=[(DataType.FLOAT64,
                           np.ones(2, np.float64))]))
        plain = wire.serialize_cycle_request(CacheCycleRequest(
            epoch=1, nslots=4, hit_mask=0b1, invalid_mask=0,
            requests=[]))
        assert wire.combine_cycle_requests([spec, plain]) is None
        assert wire.combine_cycle_requests([spec, spec]) is None

    def test_reduce_spec_sums_ranks(self):
        import numpy as np

        from horovod_tpu.common.runtime import Runtime
        frames = [CacheCycleRequest(
            epoch=0, nslots=2, hit_mask=0b11,
            spec_payload=[(DataType.FLOAT64,
                           memoryview(np.full(4, float(r + 1))))])
            for r in range(3)]
        out = Runtime._reduce_spec(frames)
        assert out[0][0] == DataType.FLOAT64
        np.testing.assert_array_equal(out[0][1], np.full(4, 6.0))

    def test_reduce_spec_rejects_layout_divergence(self):
        import numpy as np

        from horovod_tpu.common.runtime import Runtime
        a = CacheCycleRequest(epoch=0, nslots=1, hit_mask=1,
                              spec_payload=[(DataType.FLOAT64,
                                             memoryview(np.ones(4)))])
        b = CacheCycleRequest(epoch=0, nslots=1, hit_mask=1,
                              spec_payload=[(DataType.FLOAT64,
                                             memoryview(np.ones(5)))])
        with pytest.raises(ConnectionError):
            Runtime._reduce_spec([a, b])

    def test_combine_refuses_mixed_or_diverged_frames(self):
        cached = wire.serialize_cycle_request(CacheCycleRequest(
            epoch=1, nslots=4, hit_mask=0b1, invalid_mask=0,
            requests=[]))
        full = wire.serialize_cycle_request(RequestList([]))
        assert wire.combine_cycle_requests([cached, full]) is None
        other_epoch = wire.serialize_cycle_request(CacheCycleRequest(
            epoch=2, nslots=4, hit_mask=0b1, invalid_mask=0,
            requests=[]))
        assert wire.combine_cycle_requests(
            [cached, other_epoch]) is None


class TestReplay:
    def _runtime_shell(self):
        """A bare object exposing just what _unfuse/_replay_grants
        need — keeps these tests transport-free."""
        from horovod_tpu.common.runtime import Runtime
        return Runtime.__new__(Runtime)

    def test_unfuse_fused_allreduce(self):
        from horovod_tpu.common.runtime import Runtime
        fused = Response(response_type=ResponseType.ALLREDUCE,
                         tensor_names=["a", "b"], devices=[-1, -1],
                         tensor_sizes=[10, 20], prescale_factor=0.5)
        one = Runtime._unfuse(fused, 1, world_size=2)
        assert one.tensor_names == ["b"]
        assert one.tensor_sizes == [20]
        assert one.prescale_factor == 0.5
        assert one.devices == [-1, -1]

    def test_unfuse_fused_allgather_entry_major(self):
        from horovod_tpu.common.runtime import Runtime
        # 2 entries x 3 ranks, entry-major sizes
        fused = Response(response_type=ResponseType.ALLGATHER,
                         tensor_names=["g1", "g2"],
                         devices=[-1, -1, -1],
                         tensor_sizes=[3, 4, 5, 1, 1, 1])
        assert Runtime._unfuse(fused, 0, 3).tensor_sizes == [3, 4, 5]
        assert Runtime._unfuse(fused, 1, 3).tensor_sizes == [1, 1, 1]

    def test_unfuse_sizeless_response(self):
        from horovod_tpu.common.runtime import Runtime
        bc = Response(response_type=ResponseType.BROADCAST,
                      tensor_names=["w"], devices=[-1, -1])
        one = Runtime._unfuse(bc, 0, 2)
        assert one.tensor_names == ["w"] and one.tensor_sizes == []

    def test_replayed_fusion_never_mutates_cached_entries(self):
        """fuse_responses mutates the batch head's lists; the replay
        must clone before fusing or the cache would corrupt after one
        hit cycle."""
        c = ResponseCache(4)
        _put(c, "a")
        _put(c, "b")
        clones = [c.entry(s).clone_response() for s in (0, 1)]
        fused = fuse_responses(
            clones, {"a": DataType.FLOAT32, "b": DataType.FLOAT32},
            1 << 20, {"a": 1, "b": 1})
        assert fused[0].tensor_names == ["a", "b"]
        assert c.entry(0).response.tensor_names == ["a"]
        assert c.entry(1).response.tensor_names == ["b"]

    def test_iter_slots_ascending(self):
        from horovod_tpu.common.runtime import Runtime
        mask = (1 << 63) | (1 << 5) | 1
        assert list(Runtime._iter_slots(mask)) == [0, 5, 63]


class TestConfigKnobs:
    def test_env_knobs(self, monkeypatch):
        from horovod_tpu.common.config import Config
        monkeypatch.setenv("HOROVOD_CACHE_ENABLED", "0")
        monkeypatch.setenv("HOROVOD_CACHE_CAPACITY", "77")
        monkeypatch.setenv("HOROVOD_CACHE_SPECULATIVE", "0")
        c = Config.from_env()
        assert c.cache_enabled is False
        assert c.cache_capacity == 77
        assert c.cache_speculative is False

    def test_zero_capacity_disables(self):
        from horovod_tpu.common.config import Config
        from horovod_tpu.common.controller import LocalController
        from horovod_tpu.common.runtime import Runtime
        from horovod_tpu.ops.local_ops import LocalBackend
        from horovod_tpu.ops.operation_manager import OperationManager
        cfg = Config(cache_capacity=0, async_completion=False)
        rt = Runtime(cfg, LocalController(),
                     OperationManager([LocalBackend(lambda: 1)]))
        assert rt._cache is None
        assert rt.negotiation_cache_stats() == {"enabled": False}

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError):
            ResponseCache(0)


class _Payload:
    """A tensor table payload that records every conversion to numpy
    (``__array__``), or raises on one: what the speculative frame
    builder may read without a conversion is metadata alone."""

    def __init__(self, name, arr, log, nbytes=True, convertible=True):
        self._name, self._arr, self._log = name, arr, log
        self._convertible = convertible
        self.shape, self.dtype = arr.shape, arr.dtype
        if nbytes:
            self.nbytes = arr.nbytes

    def __array__(self, dtype=None, copy=None):
        if not self._convertible:
            raise AssertionError(
                f"payload {self._name} was converted before the "
                f"backend was asked")
        self._log.append(self._name)
        return self._arr


class _AskedBackend:
    """Answers fused_cycle_reducible from a rule and keeps every
    byte count it was asked with."""

    def __init__(self, rule):
        self._rule = rule
        self.asked = []

    def fused_cycle_reducible(self, nbytes):
        self.asked.append(nbytes)
        return self._rule(nbytes)


class TestSpecFrameAsksBeforeItCopies:
    """_build_spec_frame holds the whole replay plan to the backend's
    fused_cycle_reducible, asked with the batch's uncompressed bytes
    from metadata, before any payload is converted."""

    SHAPES = [(3,), (2, 4), (5,)]      # 12, 32 and 20 bytes of f32

    def _shell(self, threshold, backend, nbytes, convertible,
               dtype="float32", last_resp=None, absent=(),
               **resp_attrs):
        """A transport-free Runtime holding one steady set of cached
        allreduces in its table: just what _build_spec_frame reads.
        ``resp_attrs`` are set on every cached response and
        ``last_resp`` on the last one alone; names in ``absent`` are
        cached but not in the table."""
        import types

        import numpy as np

        from horovod_tpu.common.runtime import Runtime
        from horovod_tpu.common.tensor_table import (
            TensorTable, TensorTableEntry,
        )
        rt = Runtime.__new__(Runtime)
        rt._cache = ResponseCache(8)
        rt.parameter_manager = None
        rt.controller = types.SimpleNamespace(is_coordinator=False)
        rt._replay_epoch = -1
        rt._replay_plans = {}
        rt._world_fusion_threshold = threshold
        rt._world_id = 0
        # the serialized frame, not a SteadyPlan
        rt._steady_native_ok = False
        rt._spec_inflight = None
        rt._spec_bids = 0
        rt._spec_declines = 0
        rt._spec_declined = set()
        rt.tensor_table = TensorTable()
        rt.op_manager = types.SimpleNamespace(
            pick=lambda entries, resp: backend)
        log, arrays, bit_requests = [], [], []
        for i, shape in enumerate(self.SHAPES):
            name = f"g.{i}"
            arr = ((np.arange(int(np.prod(shape)), dtype=np.float32)
                    .reshape(shape) + 10.0 * i) / 3).astype(dtype)
            req = _req(name, shape=shape,
                       dtype=numpy_dtype_to_datatype(arr.dtype))
            resp = _resp(name, numel=arr.size)
            for attr, value in resp_attrs.items():
                setattr(resp, attr, value)
            if i == len(self.SHAPES) - 1:
                for attr, value in (last_resp or {}).items():
                    setattr(resp, attr, value)
            _put(rt._cache, name, req, resp)
            if name not in absent:
                rt.tensor_table.add(
                    TensorTableEntry(name, _Payload(
                        name, arr, log, nbytes, convertible)), req)
            arrays.append(arr)
            bit_requests.append((i, req))
        return rt, log, arrays, bit_requests

    @pytest.mark.parametrize("threshold,rule,nbytes,segments", [
        pytest.param(1 << 20, lambda n: False, True, None,
                     id="declines"),
        pytest.param(1 << 20, lambda n: False, False, None,
                     id="declines-by-request-shape"),
        pytest.param(48, lambda n: n > 20, True, None,
                     id="second-segment-declines"),
        pytest.param(1 << 20, lambda n: True, True, [[0, 1, 2]],
                     id="accepts"),
        pytest.param(1 << 20, lambda n: True, False, [[0, 1, 2]],
                     id="accepts-by-request-shape"),
        pytest.param(48, lambda n: True, True, [[0, 1], [2]],
                     id="accepts-two-segments"),
    ])
    def test_plan_is_asked_whole_then_copied(self, threshold, rule,
                                             nbytes, segments):
        import numpy as np

        backend = _AskedBackend(rule)
        declines = segments is None
        rt, log, arrays, bit_requests = self._shell(
            threshold, backend, nbytes, convertible=not declines)
        mask = 0b111
        if declines:
            for cycle in (1, 2, 3):
                assert rt._build_spec_frame(mask, bit_requests) is None
                assert rt._spec_declines == cycle
            assert log == [] and rt._spec_bids == 0
            assert rt._spec_inflight is None
            # The plan is asked in order and only as far as the first
            # "no" (sizes from nbytes or from the request alike), and
            # the answer is kept for the steady set: once, not thrice.
            want = [64] if threshold > 48 else [12 + 32, 20]
            assert backend.asked == want
            # Another fusion threshold is another plan: asked again.
            rt._world_fusion_threshold = threshold - 1
            assert rt._build_spec_frame(mask, bit_requests) is None
            assert backend.asked == want * 2 and log == []
            assert rt._spec_declines == 4
            return
        frame = rt._build_spec_frame(mask, bit_requests)
        assert rt._spec_bids == 1 and rt._spec_declines == 0
        # Asked with sum(nbytes) of what was then converted, every
        # segment before the first conversion, each payload once.
        assert backend.asked == [
            sum(arrays[i].nbytes for i in seg) for seg in segments]
        assert log == [f"g.{i}" for seg in segments for i in seg]
        # The frame the parent's order (convert, then ask) builds.
        want = wire.serialize_cycle_request(CacheCycleRequest(
            epoch=rt._cache.epoch, nslots=rt._cache.nslots,
            hit_mask=mask, spec_payload=[
                (DataType.FLOAT32,
                 np.concatenate([arrays[i].reshape(-1) for i in seg]))
                for seg in segments]))
        assert frame == want
        assert [[e.tensor_name for e in entries]
                for _, entries, _ in rt._spec_inflight] == [
            [f"g.{i}" for i in seg] for seg in segments]

    @pytest.mark.parametrize("reason", [
        "not-an-allreduce", "ring", "two-level", "int8-wire",
        "vanished-entry", "no-backend", "tuner-moved-the-plan"])
    def test_every_other_decline_converts_nothing(self, reason):
        """Each reason _spec_admitted has for "no" besides the
        backend's own, met at the LAST batch of the plan or before
        the plan is read: None, no payload converted, nothing bid,
        and (none of them being the backend's answer) nothing counted
        or kept in the memo."""
        import types

        from horovod_tpu.common import wire_dtype as wd

        backend = _AskedBackend(lambda n: True)
        last_resp = {
            "not-an-allreduce":
                {"response_type": ResponseType.BROADCAST},
            "ring": {"algorithm": wd.ALG_RING},
            "two-level": {"algorithm": wd.ALG_TWOLEVEL},
            "int8-wire": {"wire_dtype": wd.WIRE_INT8},
        }.get(reason)
        rt, log, arrays, bit_requests = self._shell(
            1 << 20, backend, True, convertible=False,
            last_resp=last_resp,
            absent=("g.2",) if reason == "vanished-entry" else ())
        first = arrays[0].nbytes + arrays[1].nbytes
        if reason == "no-backend":
            def pick(entries, resp):
                raise RuntimeError("no backend")
            rt.op_manager = types.SimpleNamespace(pick=pick)
            asked = []
        elif reason == "tuner-moved-the-plan":
            rt.parameter_manager = types.SimpleNamespace(plan_revision=2)
            rt._wire_plan_rev = 1
            rt.controller = types.SimpleNamespace(is_coordinator=True)
            asked = []
        elif reason == "vanished-entry":
            asked = []          # one batch of three, one name gone
        else:
            asked = [first]     # the batch before the odd one passed
        mask = 0b111
        assert rt._spec_admitted(mask, bit_requests) is None
        assert rt._build_spec_frame(mask, bit_requests) is None
        assert backend.asked == asked * 2 and log == []
        assert rt._spec_bids == 0 and rt._spec_inflight is None
        assert rt._spec_declines == 0 and rt._spec_declined == set()

    @pytest.mark.parametrize("native", [False, True],
                             ids=["serialized", "steady-plan"])
    @pytest.mark.parametrize("dtype,wire_name", [
        ("float32", "none"), ("float32", "bf16"), ("float32", "fp16"),
        ("float64", "none"), ("float64", "bf16"),
        ("bfloat16", "none"), ("float16", "none")])
    def test_both_renderings_hold_the_host_packs_bytes(
            self, dtype, wire_name, native):
        """_pack_spec_frame's two renderings (the serialized frame's
        segments; a SteadyPlan's packed buffers, arena and fresh)
        carry, per batch, the bytes _pack_fused + compress_send_payload
        make of the same entries: prescale applied in the tensors' own
        dtype, then one cast to the negotiated wire dtype."""
        import ml_dtypes  # noqa: F401  (registers bfloat16 by name)
        import numpy as np

        from horovod_tpu.common import steady, wire_dtype as wd
        from horovod_tpu.common.arena import FusionArena
        from horovod_tpu.ops.socket_ops import (
            _pack_fused, compress_send_payload,
        )

        w = wd.wire_code_of(wire_name)
        backend = _AskedBackend(lambda n: True)
        itemsize = np.dtype(dtype).itemsize
        rt, log, arrays, bit_requests = self._shell(
            11 * itemsize, backend, True, convertible=True, dtype=dtype,
            wire_dtype=w, prescale_factor=0.5)
        mask = 0b111
        admitted = rt._spec_admitted(mask, bit_requests)
        assert [r.tensor_names for r, _ in admitted] == [
            ["g.0", "g.1"], ["g.2"]] and log == []
        want = []
        for seg, (resp, _) in zip([[0, 1], [2]], admitted):
            fused, _ = _pack_fused([arrays[i] for i in seg], resp)
            assert fused.dtype == arrays[0].dtype
            if w:
                fused = compress_send_payload(fused, w)
                assert fused.dtype == wd.wire_np_dtype(w)
            want.append(fused.tobytes())
        if not native:
            frame = rt._pack_spec_frame(mask, admitted)
            got = wire.parse_cycle_request(frame).spec_payload
            assert [dt for dt, _ in got] == [
                wd.wire_datatype(w) if w
                else numpy_dtype_to_datatype(arrays[0].dtype)] * 2
            assert [a.tobytes() for _, a in got] == want
            assert rt._spec_bids == 1 and len(log) == 3
            return
        rt._steady_native_ok = rt._spec_ok = True
        rt._steady_plans, rt._steady_plan_epoch = {}, -1
        rt._overlap, rt._overlap_chunk = None, 0
        rt._send_arena = FusionArena()
        for bids, coordinator in enumerate((False, True), 1):
            rt.controller.is_coordinator = coordinator
            splan = rt._pack_spec_frame(mask, admitted)
            assert isinstance(splan, steady.SteadyPlan)
            plan, bufs = rt._spec_steady
            assert plan is splan and rt._spec_bids == bids
            assert [b.tobytes() for b in bufs] == want
            # workers send from the arena, the coordinator reduces
            # into buffers of its own
            assert all((b is v) != coordinator
                       for b, v in zip(bufs, splan.send_views))
            assert splan.frame_bytes(bufs) == wire.serialize_cycle_request(
                CacheCycleRequest(
                    epoch=rt._cache.epoch, nslots=rt._cache.nslots,
                    hit_mask=mask, spec_payload=[
                        (dt, b) for dt, b in zip(splan.seg_dtypes, bufs)]))

    def test_world_of_one_declines_every_steady_cycle(self, hvd_world,
                                                      monkeypatch):
        """LocalBackend has no star to ride: once the set is steady,
        every cycle declines from the sizes, converts nothing and
        bids nothing; the sums stay exact."""
        import numpy as np

        from horovod_tpu.common import basics
        from horovod_tpu.ops import socket_ops

        converted = []
        real = socket_ops._to_numpy

        def counting(tensor):
            converted.append(1)
            return real(tensor)

        monkeypatch.setattr(socket_ops, "_to_numpy", counting)
        hvd = hvd_world
        xs = [np.full(16 + i, float(i + 1), np.float32)
              for i in range(6)]

        def step():
            hs = hvd.grouped_allreduce_async(xs, average=False,
                                             name="sd")
            for x, h in zip(xs, hs):
                np.testing.assert_array_equal(hvd.synchronize(h), x)

        stats = basics.runtime().negotiation_cache_stats
        for _ in range(6):      # learn the cache, then the steady set
            step()
        before = stats()
        assert before["spec_declines"] > 0, before
        for _ in range(5):
            step()
        after = stats()
        assert after["spec_declines"] - before["spec_declines"] == 5, (
            before, after)
        assert after["spec_bids"] == 0 and converted == [], after
