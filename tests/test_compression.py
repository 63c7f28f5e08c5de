"""Wire-dtype gradient compression + two-level collectives (ISSUE 9).

Three tiers in one module:

* unit tests of the shared dtype table / wire codec / negotiation
  resolution / per-bucket autotuner grid (common/wire_dtype.py,
  coordinator.py, parameter_manager.py);
* byte-layout parity of the compressed steady plan against the Python
  serializer (the native/pure-Python interop contract);
* multi-process legs: compressed zero-copy steady state, heterogeneous
  knob negotiation (bit-exact vs a fresh all-none replay), two-level
  multi-host allreduce, SIGKILL mid-compressed-cycle fail-fast, and
  the convergence-parity training runs (none vs bf16 vs int8+EF).
"""

import json
import os
import signal

import numpy as np
import pytest

from horovod_tpu.common import wire as hwire
from horovod_tpu.common import wire_dtype as wd
from horovod_tpu.common.compression import Compression
from horovod_tpu.common.coordinator import (
    ResponseCache, construct_response, fuse_responses, MessageTable,
)
from horovod_tpu.common.message import (
    DataType, Request, RequestList, RequestType, Response, ResponseType,
)
from tests.test_multiprocess import run_scenario

_HB_ENV = {
    "HOROVOD_HEARTBEAT_INTERVAL": "0.3",
    "HOROVOD_HEARTBEAT_TIMEOUT": "3",
}
_SIGKILL_RC = -signal.SIGKILL
_SOCKET_ENV = {"HOROVOD_TPU_SHM": "0", "HOROVOD_TPU_RING_THRESHOLD": "-1"}


# -- shared dtype table (the satellite bugfix) ------------------------------

class TestSharedDtypeTable:
    def test_wire_codec_and_compression_agree_on_bfloat16(self):
        """The bug class this PR closes: compression.py's old local
        name list vs the wire codec's — ml_dtypes/jax bfloat16 must be
        floating to BOTH, via ONE table."""
        import ml_dtypes
        from horovod_tpu.common.compression import _is_floating

        class T:
            dtype = np.dtype(ml_dtypes.bfloat16)

        assert _is_floating(T())
        assert wd.is_floating(np.dtype(ml_dtypes.bfloat16))
        assert wd.is_floating(np.float32)
        assert not wd.is_floating(np.int32)

    def test_framework_cast_is_noop_while_wire_active(self):
        """Double-cast deprecation: with wire compression active the
        framework-level Compression helpers pass through."""
        x = np.ones(8, np.float32)
        wd.set_active(wd.WIRE_BF16)
        try:
            out, ctx = Compression.bf16.compress(x)
            assert out is x and ctx is None
            out, ctx = Compression.fp16.compress(x)
            assert out is x and ctx is None
        finally:
            wd.set_active(wd.WIRE_NONE)
        out, ctx = Compression.fp16.compress(x)
        assert out.dtype == np.float16  # inactive: classic cast


# -- codec ------------------------------------------------------------------

class TestCodec:
    def test_wire_code_of(self):
        assert wd.wire_code_of("bf16") == wd.WIRE_BF16
        assert wd.wire_code_of("NONE") == wd.WIRE_NONE
        with pytest.raises(ValueError):
            wd.wire_code_of("bf17")

    def test_config_rejects_typo(self):
        from horovod_tpu.common.config import Config
        os.environ["HOROVOD_COMPRESSION"] = "b16"
        try:
            with pytest.raises(ValueError):
                Config.from_env()
        finally:
            del os.environ["HOROVOD_COMPRESSION"]

    def test_resolve_common_denominator(self):
        assert wd.resolve([wd.WIRE_BF16, wd.WIRE_NONE]) == wd.WIRE_NONE
        assert wd.resolve([wd.WIRE_INT8, wd.WIRE_BF16]) == wd.WIRE_BF16
        assert wd.resolve([wd.WIRE_FP16, wd.WIRE_FP16]) == wd.WIRE_FP16
        assert wd.resolve([]) == wd.WIRE_NONE

    @pytest.mark.parametrize("wire,tol", [(wd.WIRE_BF16, 1e-2),
                                          (wd.WIRE_FP16, 1e-3)])
    def test_cast_roundtrip(self, wire, tol):
        a = np.linspace(-3, 3, 1001, dtype=np.float32)
        c = wd.compress(a, wire)
        assert c.nbytes == a.nbytes // 2
        d = wd.decompress(c, wire, np.float32, a.size)
        assert d.dtype == np.float32 and d.flags.writeable
        np.testing.assert_allclose(d, a, atol=tol)
        # bytes input (the recv path) decodes identically
        d2 = wd.decompress(bytes(memoryview(c.view(np.uint8))), wire,
                           np.float32, a.size)
        np.testing.assert_array_equal(d, d2)

    def test_int8_roundtrip_and_exact_constants(self):
        a = np.linspace(-3, 3, 1001, dtype=np.float32)
        q = wd.quantize(a)
        assert q.nbytes == a.size + 4
        d = wd.dequantize(q, np.float32, a.size)
        # quantization granularity: half a lane of max|x|/127
        np.testing.assert_allclose(d, a, atol=3.0 / 127.0 * 0.51)
        # constant tensors are exact (q == ±127)
        c = np.full(64, 7.5, np.float32)
        np.testing.assert_array_equal(
            wd.dequantize(wd.quantize(c), np.float32, 64), c)

    def test_error_feedback_bounds_drift(self):
        """DGC property: with residual feedback the ACCUMULATED
        quantized stream tracks the true accumulated gradient."""
        rng = np.random.RandomState(0)
        a = rng.randn(512).astype(np.float32)
        ef = wd.ErrorFeedback()
        acc = np.zeros_like(a)
        for _ in range(50):
            comp = ef.apply(("k",), a)
            q = wd.quantize(comp)
            ef.update(("k",), comp, q)
            acc += wd.dequantize(q, np.float32, a.size)
        drift = np.abs(acc - 50 * a).max()
        # without EF the drift would be ~50 * scale/2 ≈ 25 lanes; with
        # it, at most ~1 lane of the running residual
        assert drift <= 2 * np.abs(a).max() / 127.0, drift

    def test_error_feedback_lru_keeps_hot_keys_past_cap(self):
        """More distinct batches than the cap must evict the OLDEST
        residual, never wipe the store — a hot key's compensation
        chain survives arbitrary cold-key churn."""
        ef = wd.ErrorFeedback()
        hot = np.full(16, 0.3, np.float32)
        for i in range(3 * ef._CAP):
            comp = ef.apply(("hot",), hot)
            q = wd.quantize(comp)
            ef.update(("hot",), comp, q)
            cold = np.full(16, float(i + 1), np.float32)
            ccomp = ef.apply((f"cold{i}",), cold)
            ef.update((f"cold{i}",), ccomp, wd.quantize(ccomp))
            assert ("hot",) in ef._residuals, i
            assert len(ef._residuals) <= ef._CAP

    def test_reduce_wire_bf16_matches_sequential_sum(self):
        rng = np.random.RandomState(1)
        parts = [rng.randn(256).astype(np.float32) for _ in range(4)]
        wires = [wd.compress(p, wd.WIRE_BF16) for p in parts]
        acc = np.array(wires[0], copy=True)
        out = wd.reduce_wire(acc, wires[1:], wd.WIRE_BF16,
                             np.float32, 256)
        ref = wires[0].astype(np.float32)
        for w in wires[1:]:
            ref = (ref + w.astype(np.float32)).astype(
                wires[0].dtype).astype(np.float32)
        np.testing.assert_allclose(out.astype(np.float32), ref)

    def test_reduce_wire_int8_requantizes_world_sum(self):
        rng = np.random.RandomState(2)
        parts = [rng.randn(256).astype(np.float32) for _ in range(4)]
        bufs = [wd.quantize(p) for p in parts]
        out = wd.reduce_wire(bufs[0], bufs[1:], wd.WIRE_INT8,
                             np.float32, 256)
        got = wd.dequantize(out, np.float32, 256)
        want = sum(wd.dequantize(b, np.float32, 256) for b in bufs)
        np.testing.assert_allclose(got, want,
                                   atol=np.abs(want).max() / 127.0)

    def test_native_cast_matches_numpy_round_to_nearest_even(self):
        from horovod_tpu import native
        if native.get() is None or not hasattr(native.get(),
                                               "hvd_cast"):
            pytest.skip("native core unavailable")
        import ml_dtypes
        rng = np.random.RandomState(3)
        a = rng.randn(4096).astype(np.float32)
        b = np.empty(4096, ml_dtypes.bfloat16)
        assert native.cast_into(a, b)
        np.testing.assert_array_equal(
            b.view(np.uint16), a.astype(ml_dtypes.bfloat16).view(
                np.uint16))
        h = np.empty(4096, np.float16)
        assert native.cast_into(a, h)
        np.testing.assert_array_equal(h, a.astype(np.float16))


# -- negotiation ------------------------------------------------------------

def _req(rank, wire, name="t", dtype=DataType.FLOAT32, shape=(8,)):
    return Request(request_rank=rank, request_type=RequestType.ALLREDUCE,
                   tensor_type=dtype, tensor_name=name,
                   tensor_shape=shape, wire_dtype=wire)


class TestNegotiation:
    def test_construct_response_resolves_min(self):
        table = MessageTable()
        for r, w in enumerate((wd.WIRE_INT8, wd.WIRE_BF16,
                               wd.WIRE_INT8)):
            table.increment_tensor_count(_req(r, w), 3)
        resp = construct_response(table, "t", 3)
        assert resp.wire_dtype == wd.WIRE_BF16

    def test_one_rank_uncompressed_degrades_batch(self):
        table = MessageTable()
        for r, w in enumerate((wd.WIRE_BF16, wd.WIRE_NONE,
                               wd.WIRE_BF16)):
            table.increment_tensor_count(_req(r, w), 3)
        assert construct_response(table, "t", 3).wire_dtype \
            == wd.WIRE_NONE

    def test_incompressible_dtype_never_compresses(self):
        table = MessageTable()
        for r in range(2):
            table.increment_tensor_count(
                _req(r, wd.WIRE_BF16, dtype=DataType.INT32), 2)
        assert construct_response(table, "t", 2).wire_dtype \
            == wd.WIRE_NONE

    def test_wire_rides_request_and_response_codec(self):
        req = _req(1, wd.WIRE_INT8)
        rl = hwire.parse_request_list(
            hwire.serialize_request_list(RequestList([req])))
        assert rl.requests[0].wire_dtype == wd.WIRE_INT8
        resp = Response(response_type=ResponseType.ALLREDUCE,
                        tensor_names=["t"], tensor_sizes=[8],
                        wire_dtype=wd.WIRE_BF16,
                        algorithm=wd.ALG_TWOLEVEL)
        from horovod_tpu.common.message import ResponseList
        out = hwire.parse_response_list(
            hwire.serialize_response_list(ResponseList([resp])))
        assert out.responses[0].wire_dtype == wd.WIRE_BF16
        assert out.responses[0].algorithm == wd.ALG_TWOLEVEL

    def test_cache_signature_includes_wire_dtype(self):
        """A knob change must renegotiate, not replay a stale
        compression verdict."""
        cache = ResponseCache(8)
        req = _req(0, wd.WIRE_BF16)
        cache.put("t", ResponseCache.signature(req),
                  Response(response_type=ResponseType.ALLREDUCE,
                           tensor_names=["t"], tensor_sizes=[8]),
                  DataType.FLOAT32, 1)
        state, _ = cache.lookup(req)
        assert state == ResponseCache.HIT
        state, _ = cache.lookup(_req(0, wd.WIRE_NONE))
        assert state == ResponseCache.INVALID

    def test_fusion_keeps_mixed_verdicts_apart(self):
        def resp(name, wire=0, alg=0):
            return Response(response_type=ResponseType.ALLREDUCE,
                            tensor_names=[name], tensor_sizes=[8],
                            devices=[0, 0], wire_dtype=wire,
                            algorithm=alg)
        dtypes = {n: DataType.FLOAT32 for n in "abcd"}
        fused = fuse_responses(
            [resp("a", wd.WIRE_BF16), resp("b", wd.WIRE_NONE),
             resp("c", wd.WIRE_BF16), resp("d", alg=wd.ALG_TWOLEVEL)],
            dtypes, 1 << 20, {n: 1 for n in "abcd"})
        names = sorted(tuple(f.tensor_names) for f in fused)
        assert ("a", "c") in names      # same verdict fuses
        assert ("b",) in names and ("d",) in names

    def test_static_policy(self):
        p = wd.StaticWirePolicy(True, 1 << 20, multi_host=True)
        assert p.plan(2 << 20) == (wd.ALG_TWOLEVEL, None)
        assert p.plan(4096) == (wd.ALG_DEFAULT, None)
        p2 = wd.StaticWirePolicy(True, 0, multi_host=False)
        assert p2.plan(2 << 20) == (wd.ALG_DEFAULT, None)


# -- per-bucket autotuner grid ----------------------------------------------

class TestBucketTuner:
    def test_converges_to_best_combo_and_skips_idle_buckets(self):
        from horovod_tpu.common.parameter_manager import _BucketTuner
        combos = [(wd.ALG_DEFAULT, wd.WIRE_NONE),
                  (wd.ALG_DEFAULT, wd.WIRE_BF16),
                  (wd.ALG_RING, wd.WIRE_NONE),
                  (wd.ALG_RING, wd.WIRE_BF16),
                  (wd.ALG_TWOLEVEL, wd.WIRE_NONE),
                  (wd.ALG_TWOLEVEL, wd.WIRE_BF16)]
        t = _BucketTuner(combos, 3)
        quality = {(wd.ALG_TWOLEVEL, wd.WIRE_BF16): 4.0,
                   (wd.ALG_RING, wd.WIRE_BF16): 2.0}
        guard = 0
        while not t.done:
            guard += 1
            assert guard < 100
            if t.bucket < 2:
                t.feed(1.0, 0)      # idle bucket: no traffic
            else:
                t.feed(quality.get(t.current_combo(), 1.0), 1 << 20)
        assert t.plan[0] == (wd.ALG_DEFAULT, None)   # idle kept default
        assert t.plan[1] == (wd.ALG_DEFAULT, None)
        assert t.plan[2] == (wd.ALG_TWOLEVEL, wd.WIRE_BF16)

    def test_parameter_manager_grid_then_bayes(self):
        """The grid phase settles the bucket table, then the
        continuous BO phase still converges — tuning ends once."""
        from horovod_tpu.common.config import Config
        from horovod_tpu.common.controller import LocalController
        from horovod_tpu.common.parameter_manager import ParameterManager
        cfg = Config()
        cfg.autotune = True
        cfg.autotune_warmup_samples = 1
        cfg.autotune_steps_per_sample = 2
        cfg.autotune_bayes_opt_max_samples = 3
        pm = ParameterManager(cfg, LocalController())
        pm.configure_wire(wd.WIRE_BF16, multi_host=False, world_size=2)
        # world_size 2 + single host: grid = default x {none, bf16}
        for _ in range(2000):
            pm.plan(2 << 20)
            pm.on_cycle(2 << 20)
            if not pm.tuning:
                break
        assert not pm.tuning
        plan = pm.bucket_plan()
        assert plan[2][0] == wd.ALG_DEFAULT
        assert plan[2][1] in (wd.WIRE_NONE, wd.WIRE_BF16)

    def test_wire_candidates_never_exceed_proposal(self):
        from horovod_tpu.common.config import Config
        from horovod_tpu.common.controller import LocalController
        from horovod_tpu.common.parameter_manager import ParameterManager
        cfg = Config()
        cfg.autotune = True
        pm = ParameterManager(cfg, LocalController())
        pm.configure_wire(wd.WIRE_NONE, multi_host=False, world_size=2)
        # nothing to explore: single combo -> no tuner armed
        assert pm._bucket_tuner is None


# -- compressed steady-plan byte parity -------------------------------------

class TestCompressedSteadyPlan:
    def test_frame_bytes_match_python_serializer(self):
        """The native steady cycle byte-compares frames against
        wire.spec_frame_parts; a COMPRESSED plan must serialize to
        exactly what the Python path would send for the same compressed
        segments — one layout, two implementations."""
        import ml_dtypes
        from horovod_tpu.common.arena import FusionArena
        from horovod_tpu.common.message import CacheCycleRequest
        from horovod_tpu.common.steady import SteadyPlan
        arena = FusionArena()
        rng = np.random.RandomState(7)
        arrays = [rng.randn(64).astype(np.float32),
                  rng.randn(32).astype(np.float32)]
        count = 96
        plan = SteadyPlan(
            epoch=5, nslots=8, mask=0b11,
            segments=[(DataType.BFLOAT16, np.dtype(ml_dtypes.bfloat16),
                       count * 2, np.dtype(np.float32))],
            arena=arena)
        bufs = plan.pack([arrays], [1.0], use_arena=True)
        assert bufs[0].dtype == np.dtype(ml_dtypes.bfloat16)
        frame = plan.frame_bytes(bufs)
        fused = np.concatenate(arrays)
        ref = hwire.serialize_cycle_request(CacheCycleRequest(
            epoch=5, nslots=8, hit_mask=0b11,
            spec_payload=[(DataType.BFLOAT16,
                           fused.astype(ml_dtypes.bfloat16))]))
        assert frame == ref
        # and the segment decompresses back within bf16 tolerance
        got = wd.decompress(bufs[0], wd.WIRE_BF16, np.float32, count)
        np.testing.assert_allclose(got, fused, atol=0.03)

    def test_prescale_applies_before_cast(self):
        import ml_dtypes
        from horovod_tpu.common.arena import FusionArena
        from horovod_tpu.common.steady import SteadyPlan
        arrays = [np.full(16, 3.0, np.float32)]
        plan = SteadyPlan(
            epoch=0, nslots=4, mask=1,
            segments=[(DataType.BFLOAT16, np.dtype(ml_dtypes.bfloat16),
                       32, np.dtype(np.float32))],
            arena=FusionArena())
        bufs = plan.pack([arrays], [0.5], use_arena=False)
        np.testing.assert_allclose(
            bufs[0].astype(np.float32), 1.5)


# -- multi-process legs -----------------------------------------------------

def test_compressed_steady_zero_copy():
    """bf16 wire on the fused speculative / native zero-copy steady
    path at ws=4: exact values, hvd_data_copies_total == 0, wire bytes
    measurably saved (the ISSUE 9 zero-copy-composition contract)."""
    run_scenario(
        "compression_steady_zero_copy", 4, timeout=120.0,
        extra_env={**_SOCKET_ENV,
                   "HOROVOD_COMPRESSION": "bf16",
                   "HOROVOD_TPU_METRICS": "1"})


def test_compression_hetero_negotiates_common_denominator(tmp_path):
    """One rank proposing bf16 in an otherwise-uncompressed world:
    the verdict degrades to none and the run is BIT-EXACT with a
    fresh all-none world replaying the same submissions."""
    mixed = str(tmp_path / "mixed.npy")
    plain = str(tmp_path / "plain.npy")
    run_scenario(
        "compression_hetero", 3, timeout=90.0,
        extra_env={**_SOCKET_ENV, "HOROVOD_TPU_METRICS": "1",
                   "HVD_COMPRESSION_OUT": mixed},
        per_rank_env=lambda rank: (
            {"HOROVOD_COMPRESSION": "bf16"} if rank == 1 else {}))
    run_scenario(
        "compression_hetero", 3, timeout=90.0,
        extra_env={**_SOCKET_ENV, "HOROVOD_TPU_METRICS": "1",
                   "HVD_COMPRESSION_OUT": plain})
    a = np.load(mixed)
    b = np.load(plain)
    np.testing.assert_array_equal(a, b)


def test_twolevel_allreduce_multihost():
    """Two fake hosts x two ranks: HOROVOD_TWO_LEVEL=1 routes
    allreduce through local shm reduce -> roots ring -> local shm
    broadcast, with the cross leg compressed at bf16."""
    run_scenario(
        "twolevel_allreduce", 4, timeout=120.0,
        extra_env={"HOROVOD_TWO_LEVEL": "1",
                   "HOROVOD_COMPRESSION": "bf16",
                   "HOROVOD_TPU_METRICS": "1"},
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


def test_abort_sigkill_mid_compressed_cycle():
    """SIGKILL a rank deep in COMPRESSED bitmask steady state: the
    survivors must still raise WorldAbortedError naming the dead rank
    within the heartbeat deadline — the PR 2 fail-fast invariant
    holds with compression engaged (ISSUE 9 acceptance)."""
    run_scenario(
        "abort_sigkill_cached", 3, timeout=60.0,
        extra_env={**_HB_ENV, **_SOCKET_ENV,
                   "HOROVOD_COMPRESSION": "bf16",
                   "HOROVOD_FAULT_SPEC": "rank=1:kill:op=40"},
        expect_rc={1: _SIGKILL_RC})


def _train_world(tmp_path, tag: str, compression: str) -> dict:
    out = str(tmp_path / f"parity_{tag}.json")
    run_scenario(
        "compression_train_parity", 4, timeout=240.0,
        extra_env={"HOROVOD_COMPRESSION": compression,
                   "HVD_COMPRESSION_OUT": out})
    with open(out) as f:
        return json.load(f)


@pytest.mark.slow
@pytest.mark.time_limit(750)  # three worlds of _train_world's 240 s
def test_convergence_parity_none_bf16_int8(tmp_path):
    """The ISSUE 9 convergence-parity leg: the toy TransformerLM from
    models/ trained data-parallel at ws=4 under none / bf16 /
    int8+error-feedback wire dtypes must land at the same final loss
    within tolerance — compression changes bytes, not training."""
    base = _train_world(tmp_path, "none", "none")
    bf16 = _train_world(tmp_path, "bf16", "bf16")
    int8 = _train_world(tmp_path, "int8", "int8")
    l0 = base["final_loss"]
    assert np.isfinite(l0)
    # training must actually have progressed in every world
    for world in (base, bf16, int8):
        assert world["losses"][-1] < world["losses"][0], world
    assert abs(bf16["final_loss"] - l0) <= 0.05 * abs(l0) + 1e-3, \
        (l0, bf16["final_loss"])
    assert abs(int8["final_loss"] - l0) <= 0.15 * abs(l0) + 1e-3, \
        (l0, int8["final_loss"])
