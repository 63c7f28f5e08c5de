"""Single-process (size-1) public API tests: lifecycle, sync/async ops,
handles, duplicate-name errors (reference analog: single-process legs of
test/test_torch.py:59-1163 / test_tensorflow.py:63-766)."""

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.common.status import HorovodInternalError


class TestBasics:
    def test_init_shutdown(self, hvd_world):
        assert hvd.initialized()
        assert hvd.rank() == 0
        assert hvd.size() == 1
        assert hvd.local_rank() == 0
        assert hvd.local_size() == 1
        assert hvd.cross_rank() == 0
        assert hvd.cross_size() == 1
        assert hvd.is_homogeneous()
        assert hvd.mpi_threads_supported()

    def test_uninitialized_raises(self):
        hvd.shutdown()
        with pytest.raises(ValueError):
            hvd.rank()

    def test_double_init_is_noop(self, hvd_world):
        hvd.init()
        assert hvd.size() == 1


class TestOpsSize1:
    def test_allreduce_average_identity(self, hvd_world):
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        out = hvd.allreduce(x, average=True)
        np.testing.assert_allclose(out, x)

    def test_allreduce_sum_identity(self, hvd_world):
        x = np.random.randn(5).astype(np.float64)
        out = hvd.allreduce(x, average=False)
        np.testing.assert_allclose(out, x)

    def test_allreduce_prescale(self, hvd_world):
        x = np.ones(4, np.float32)
        out = hvd.allreduce(x, op=hvd.Sum, prescale_factor=2.0)
        np.testing.assert_allclose(out, 2 * x)

    def test_allgather_identity(self, hvd_world):
        x = np.random.randn(6, 2).astype(np.float32)
        np.testing.assert_allclose(hvd.allgather(x), x)

    def test_broadcast_identity(self, hvd_world):
        x = np.random.randn(2, 2)
        np.testing.assert_allclose(hvd.broadcast(x, root_rank=0), x)

    def test_async_poll_synchronize(self, hvd_world):
        x = np.ones(1000, np.float32)
        h = hvd.allreduce_async(x, average=False, name="async_t")
        while not hvd.poll(h):
            pass
        out = hvd.synchronize(h)
        np.testing.assert_allclose(out, x)

    def test_many_tensors_fused(self, hvd_world):
        handles = [hvd.allreduce_async(np.full(10, i, np.float32),
                                       average=False, name=f"fuse/{i}")
                   for i in range(50)]
        for i, h in enumerate(handles):
            np.testing.assert_allclose(hvd.synchronize(h),
                                       np.full(10, i, np.float32))

    def test_duplicate_name_raises(self, hvd_world):
        # (reference: operations.cc:1459-1462 DUPLICATE_NAME_ERROR;
        # test/test_torch.py:356) — two in-flight ops, same name.
        x = np.ones(4, np.float32)
        h1 = hvd.allreduce_async(x, name="dup")
        h2 = hvd.allreduce_async(x, name="dup")
        statuses = []
        for h in (h1, h2):
            try:
                hvd.synchronize(h)
                statuses.append("ok")
            except HorovodInternalError as e:
                statuses.append("err")
                assert "same name" in str(e)
        # The first generally wins, but at minimum exactly one must fail.
        assert statuses.count("err") >= 1

    def test_jax_array_roundtrip(self, hvd_world):
        import jax.numpy as jnp
        x = jnp.arange(8, dtype=jnp.float32)
        out = hvd.allreduce(x, average=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x))

    def test_bfloat16_allreduce(self, hvd_world):
        import ml_dtypes
        x = np.ones(16, ml_dtypes.bfloat16)
        out = hvd.allreduce(x, average=False)
        assert out.dtype == x.dtype
        np.testing.assert_allclose(np.asarray(out, np.float32), 1.0)

    def test_integer_average_rejected(self, hvd_world):
        # averaging would truncate the 1/size factor to 0 in the tensor
        # dtype — must be a loud error, not silent zeros
        with pytest.raises(ValueError, match="integer"):
            hvd.allreduce(np.arange(4, dtype=np.int64), average=True)
        with pytest.raises(ValueError, match="integer"):
            hvd.allreduce(np.arange(4, dtype=np.int32), op=hvd.Sum,
                          prescale_factor=0.5)

    def test_alltoall_identity(self, hvd_world):
        x = np.arange(6, dtype=np.float32)
        np.testing.assert_allclose(hvd.alltoall(x), x)

    def test_reducescatter_identity(self, hvd_world):
        x = np.arange(6, dtype=np.float32)
        np.testing.assert_allclose(hvd.reducescatter(x), x)


class TestCompression:
    def test_fp16_roundtrip(self):
        from horovod_tpu import Compression
        x = np.random.randn(10).astype(np.float32)
        c, ctx = Compression.fp16.compress(x)
        assert c.dtype == np.float16
        d = Compression.fp16.decompress(c, ctx)
        assert d.dtype == np.float32
        np.testing.assert_allclose(d, x, atol=1e-2)

    def test_bf16_roundtrip(self):
        import ml_dtypes
        from horovod_tpu import Compression
        x = np.random.randn(10).astype(np.float32)
        c, ctx = Compression.bf16.compress(x)
        assert c.dtype == ml_dtypes.bfloat16
        d = Compression.bf16.decompress(c, ctx)
        assert d.dtype == np.float32
        np.testing.assert_allclose(d, x, atol=1e-1)

    def test_none_passthrough(self):
        from horovod_tpu import Compression
        x = np.random.randn(4).astype(np.float32)
        c, ctx = Compression.none.compress(x)
        assert c is x
        assert Compression.none.decompress(c, ctx) is x

    def test_int_not_compressed(self):
        from horovod_tpu import Compression
        x = np.arange(4, dtype=np.int64)
        c, ctx = Compression.fp16.compress(x)
        assert c.dtype == np.int64


class TestIdleBackoff:
    def test_idle_loop_backs_off_and_wakes_on_enqueue(self, monkeypatch):
        """After the grace period the negotiation loop must slow to the
        backoff cap instead of waking every cycle, and an enqueue must
        snap it awake (so submit latency never pays the backoff)."""
        import time
        import horovod_tpu as hvd
        from horovod_tpu.common import basics as _b
        hvd.shutdown()
        monkeypatch.setenv("HOROVOD_CYCLE_TIME", "1")
        monkeypatch.setenv("HOROVOD_TPU_IDLE_BACKOFF", "25")
        hvd.init()
        try:
            rt = _b.runtime()
            time.sleep(0.3)  # pass the grace period
            c0 = rt._cycle_count
            time.sleep(0.5)
            idle_rate = rt._cycle_count - c0
            # 1 ms cycles would be ~500; the 25 ms cap bounds it to ~20
            assert idle_rate < 120, idle_rate
            # wake-on-enqueue: completion far faster than the backoff
            # window would allow if the loop stayed asleep
            t0 = time.monotonic()
            out = hvd.allreduce(np.ones(4, np.float32), average=False,
                                name="wake.test")
            latency = time.monotonic() - t0
            np.testing.assert_allclose(out, 1.0)
            assert latency < 1.0, latency
        finally:
            hvd.shutdown()

    def test_backoff_disabled_keeps_full_cycle_rate(self, monkeypatch):
        """Relative comparison (same process, back to back) so host
        slowness cancels out: the backoff-off loop must cycle several
        times faster than the backed-off loop."""
        import time
        import horovod_tpu as hvd
        from horovod_tpu.common import basics as _b

        def idle_rate(backoff_ms):
            hvd.shutdown()
            monkeypatch.setenv("HOROVOD_CYCLE_TIME", "1")
            monkeypatch.setenv("HOROVOD_TPU_IDLE_BACKOFF",
                               str(backoff_ms))
            hvd.init()
            try:
                rt = _b.runtime()
                time.sleep(0.3)  # pass the grace period
                c0 = rt._cycle_count
                t0 = time.monotonic()
                time.sleep(0.5)
                return (rt._cycle_count - c0) / (time.monotonic() - t0)
            finally:
                hvd.shutdown()

        rate_off = idle_rate(0)
        rate_on = idle_rate(25)
        assert rate_off > 3 * rate_on, (rate_off, rate_on)


class TestConfigValidation:
    def test_xla_bcast_rendering_validated(self, monkeypatch):
        """A typo'd HOROVOD_XLA_BCAST must raise, not silently pick a
        rendering — per-rank divergence would compile mismatched
        collectives for the same negotiated broadcast."""
        import pytest
        from horovod_tpu.common.config import Config

        monkeypatch.setenv("HOROVOD_XLA_BCAST", "Tree")
        assert Config.from_env().xla_broadcast == "tree"  # case-folded
        monkeypatch.setenv("HOROVOD_XLA_BCAST", "ppermute")
        with pytest.raises(ValueError, match="HOROVOD_XLA_BCAST"):
            Config.from_env()


class TestRaggedPsumDecision:
    """Skew guard for the fused variable-dim0 allgather on the XLA
    plane (reference behavior target: MPI_Allgatherv moves true bytes,
    mpi_operations.cc:95-173)."""

    def test_heavy_skew_picks_psum(self):
        from horovod_tpu.ops.xla_ops import ragged_psum_wins
        # 1 rank with 64 rows, 7 with 1: padded = 8*64, psum = 2*(71+64)
        sizes = [64, 1, 1, 1, 1, 1, 1, 1]
        assert ragged_psum_wins(sizes, [1], 8)

    def test_uniform_keeps_padded_gather(self):
        from horovod_tpu.ops.xla_ops import ragged_psum_wins
        assert not ragged_psum_wins([4] * 8, [1], 8)
        # mild skew below the ~2x-mean crossover
        assert not ragged_psum_wins([6, 4, 4, 4, 4, 4, 4, 4], [1], 8)

    def test_two_rank_world_never_psum(self):
        from horovod_tpu.ops.xla_ops import ragged_psum_wins
        # psum's 2x true bytes can't beat 2 x max at N=2
        assert not ragged_psum_wins([1024, 1], [8], 2)
        assert not ragged_psum_wins([4, 4], [8], 1)

    def test_fused_batch_accounts_all_entries(self):
        from horovod_tpu.ops.xla_ops import ragged_psum_wins
        # entry 0 skewed, entry 1 uniform and large: batch-level byte
        # totals decide (uniform bulk outweighs the skewed entry)
        sizes = [64, 1, 1, 1] + [256, 256, 256, 256]
        assert not ragged_psum_wins(sizes, [1, 64], 4)


class TestMeshExecutableCache:
    """XlaMeshBackend keeps one compiled program per key, and the key
    holds everything a program bakes in (shapes, scales, the
    negotiated verdict): an executable never goes stale, so nothing
    but the size bound ever drops one."""

    def _backend(self):
        """The mesh backend over a one-device mesh of this process: a
        world of one runs the same jit(shard_map(psum)) path."""
        import jax

        from horovod_tpu.common.metrics import MetricsRegistry
        from horovod_tpu.compat import jaxshim
        from horovod_tpu.ops import xla_ops

        class _Ctl:
            rank = 0
            size = 1
        b = xla_ops.XlaMeshBackend(_Ctl())
        dev = jax.devices()[0]
        b._mesh = jaxshim.make_raw_mesh(np.array([dev]), (xla_ops._AXIS,))
        b._my_device = dev
        b.attach_metrics(MetricsRegistry())
        return b

    @staticmethod
    def _allreduce(b, n, algorithm=0, wire_dtype=0):
        import types

        import jax.numpy as jnp

        from horovod_tpu.common.message import Response, ResponseType
        x = jnp.arange(n, dtype=jnp.float32)
        e = types.SimpleNamespace(tensor=x, output=None, callback=None,
                                  tensor_name=f"t{n}")
        resp = Response(response_type=ResponseType.ALLREDUCE,
                        tensor_names=[e.tensor_name],
                        algorithm=algorithm, wire_dtype=wire_dtype)
        assert b.execute_allreduce([e], resp).ok()
        np.testing.assert_array_equal(np.asarray(e.output), np.asarray(x))

    def test_verdict_in_signature(self):
        from horovod_tpu.common import wire_dtype as wd
        from horovod_tpu.common.message import Response
        from horovod_tpu.ops.xla_ops import XlaMeshBackend
        sig = XlaMeshBackend._verdict_sig
        r1 = Response(wire_dtype=wd.WIRE_BF16, algorithm=wd.ALG_RING)
        r2 = Response(wire_dtype=wd.WIRE_NONE, algorithm=wd.ALG_RING)
        r3 = Response(wire_dtype=wd.WIRE_BF16, algorithm=wd.ALG_TWOLEVEL)
        assert sig(r1) != sig(r2) and sig(r1) != sig(r3)
        assert sig(None) == ()

    def test_another_tensors_response_keeps_the_first_executable(self):
        """A newly negotiated response (another tensor, another cache
        epoch) must not cost the first tensor its program."""
        b = self._backend()
        self._allreduce(b, 4)
        (first,) = b._cache
        self._allreduce(b, 6)
        assert first in b._cache and len(b._cache) == 2
        self._allreduce(b, 4)
        assert b._m_compiles.value == 2 == b._m_cache_size.value

    def test_same_shape_under_two_verdicts_keeps_two_executables(self):
        from horovod_tpu.common import wire_dtype as wd
        b = self._backend()
        for _ in range(2):
            self._allreduce(b, 4)
            self._allreduce(b, 4, wd.ALG_TWOLEVEL, wd.WIRE_BF16)
        assert len(b._cache) == 2 == b._m_compiles.value
        assert {k[-1] for k in b._cache} == {
            (wd.WIRE_NONE, wd.ALG_DEFAULT),
            (wd.WIRE_BF16, wd.ALG_TWOLEVEL)}

    def test_cache_is_bounded_oldest_first(self):
        from horovod_tpu.ops.xla_ops import _CACHE_MAX
        b = self._backend()
        for i in range(_CACHE_MAX + 3):
            assert b._compiled(("k", i), lambda i=i: i) == i
        assert len(b._cache) == _CACHE_MAX == b._m_cache_size.value
        assert list(b._cache)[0] == ("k", 3)
        assert b._compiled(("k", 3), lambda: "rebuilt") == 3
        assert b._compiled(("k", 0), lambda: "rebuilt") == "rebuilt"
        assert b._m_compiles.value == _CACHE_MAX + 4
