"""ICI-native pod-scale data plane (ISSUE 18).

Three tiers in one module:

* unit tests of the ALG_ICI verdict plumbing: StaticWirePolicy
  stamping + threshold ordering, the autotune discrete-grid entry,
  the XLA executable-cache key bugfix (verdict in the key + epoch
  eviction), and SteadyPlan.adopt_packed's byte-compat validation;
* in-process IciPlane legs over the conftest-forced 8-device host
  mesh: fused_pack bit-exactness against the numpy host pack,
  compile-count flatness across replays, the pod-mode
  fused_reduce_partials psum, and epoch-bump eviction;
* multi-process legs: the fused-psum steady cycle end to end
  (ici_cycles advancing on a flat compile count, ALG_ICI provably
  stamped, data-copies delta 0), bit-exactness vs an all-socket
  replay, world-consistent degrade in a heterogeneous world, and
  SIGKILL mid-ICI-cycle fail-fast.
"""

import os
import signal

import numpy as np
import pytest

from horovod_tpu.common import wire_dtype as wd
from tests.test_multiprocess import run_scenario

_HB_ENV = {
    "HOROVOD_HEARTBEAT_INTERVAL": "0.3",
    "HOROVOD_HEARTBEAT_TIMEOUT": "3",
}
_SIGKILL_RC = -signal.SIGKILL
_SOCKET_ENV = {"HOROVOD_TPU_SHM": "0", "HOROVOD_TPU_RING_THRESHOLD": "-1"}
# The spawned worlds inherit conftest's forced 8-device XLA_FLAGS;
# restating it here keeps the wrappers green under a bare pytest
# invocation that bypassed conftest's env mutation.
_FORCED_DEVS = "--xla_force_host_platform_device_count=8"
_ICI_ENV = {**_SOCKET_ENV,
            "HOROVOD_TPU_ICI": "1",
            "HOROVOD_TPU_METRICS": "1",
            "XLA_FLAGS": _FORCED_DEVS}


# -- verdict plumbing -------------------------------------------------------

class TestStaticPolicyIci:
    def test_stamps_ici_when_world_agreed(self):
        pol = wd.StaticWirePolicy(two_level=False, threshold_bytes=0,
                                  multi_host=False, ici_allowed=True)
        alg, cap = pol.plan(1024)
        assert alg == wd.ALG_ICI
        assert cap is None

    def test_ici_threshold_gates_small_batches(self):
        pol = wd.StaticWirePolicy(two_level=False, threshold_bytes=0,
                                  multi_host=False, ici_allowed=True,
                                  ici_threshold_bytes=4096)
        assert pol.plan(4095)[0] == wd.ALG_DEFAULT
        assert pol.plan(4096)[0] == wd.ALG_ICI

    def test_ici_outranks_two_level(self):
        pol = wd.StaticWirePolicy(two_level=True, threshold_bytes=0,
                                  multi_host=True, shm_enabled=True,
                                  ici_allowed=True)
        assert pol.plan(1 << 20)[0] == wd.ALG_ICI

    def test_without_agreement_two_level_keeps_winning(self):
        pol = wd.StaticWirePolicy(two_level=True, threshold_bytes=0,
                                  multi_host=True, shm_enabled=True,
                                  ici_allowed=False)
        assert pol.plan(1 << 20)[0] == wd.ALG_TWOLEVEL

    def test_config_knobs_parse(self, monkeypatch):
        from horovod_tpu.common.config import Config
        monkeypatch.setenv("HOROVOD_TPU_ICI", "1")
        monkeypatch.setenv("HOROVOD_TPU_ICI_DEVICES", "4")
        monkeypatch.setenv("HOROVOD_TPU_ICI_THRESHOLD", "65536")
        c = Config.from_env()
        assert c.ici_enabled
        assert c.ici_devices == 4
        assert c.ici_threshold_bytes == 65536


class TestAutotuneGridIci:
    def _pm(self):
        from horovod_tpu.common.config import Config
        from horovod_tpu.common.controller import LocalController
        from horovod_tpu.common.parameter_manager import ParameterManager
        cfg = Config()
        cfg.autotune = True
        return ParameterManager(cfg, LocalController())

    def test_grid_includes_ici_when_allowed(self):
        pm = self._pm()
        pm.configure_wire(wd.WIRE_BF16, multi_host=False, world_size=2,
                          ici_allowed=True)
        combos = pm._bucket_tuner._combos
        assert (wd.ALG_ICI, wd.WIRE_NONE) in combos
        assert (wd.ALG_ICI, wd.WIRE_BF16) in combos

    def test_grid_omits_ici_without_world_agreement(self):
        pm = self._pm()
        pm.configure_wire(wd.WIRE_BF16, multi_host=False, world_size=2,
                          ici_allowed=False)
        tuner = pm._bucket_tuner
        combos = tuner._combos if tuner is not None else []
        assert not any(a == wd.ALG_ICI for a, _ in combos)


class TestMeshCacheKeyBugfix:
    """The satellite bugfix: compiled executables must be keyed on the
    NEGOTIATED verdict (wire dtype + algorithm), and evicted on the
    ResponseCache epoch bump."""

    def _backend(self):
        from horovod_tpu.ops.xla_ops import XlaMeshBackend

        class _Ctl:
            rank = 0
            size = 2
        return XlaMeshBackend(_Ctl())

    def test_verdict_in_signature(self):
        from horovod_tpu.common.message import Response
        b = self._backend()
        r1 = Response()
        r1.wire_dtype = wd.WIRE_BF16
        r1.algorithm = wd.ALG_ICI
        r2 = Response()
        r2.wire_dtype = wd.WIRE_NONE
        r2.algorithm = wd.ALG_ICI
        assert b._verdict_sig(r1) != b._verdict_sig(r2)
        r3 = Response()
        r3.wire_dtype = wd.WIRE_BF16
        r3.algorithm = wd.ALG_STAR
        assert b._verdict_sig(r1) != b._verdict_sig(r3)
        assert b._verdict_sig(None) == ()

    def test_epoch_bump_evicts_compiled_cache(self):
        b = self._backend()
        b.note_cache_epoch(0)
        b._cache[("allreduce", (4,), "float32", (), 1, ())] = object()
        b.note_cache_epoch(0)   # same epoch: keep
        assert b._cache
        b.note_cache_epoch(1)   # bump: evict
        assert not b._cache

    def test_operation_manager_fans_epoch_out(self):
        from horovod_tpu.ops.operation_manager import OperationManager

        class _B:
            def __init__(self):
                self.seen = []

            def note_cache_epoch(self, epoch):
                self.seen.append(epoch)

        class _Plain:
            pass

        b = _B()
        om = OperationManager([_Plain(), b])
        om.note_cache_epoch(7)
        assert b.seen == [7]


class TestAdoptPacked:
    def _plan(self):
        import ml_dtypes
        from horovod_tpu.common.arena import FusionArena
        from horovod_tpu.common.message import DataType
        from horovod_tpu.common.steady import SteadyPlan
        return SteadyPlan(
            epoch=3, nslots=8, mask=0b11,
            segments=[(DataType.BFLOAT16, np.dtype(ml_dtypes.bfloat16),
                       64, np.dtype(np.float32)),
                      (DataType.FLOAT32, np.dtype(np.float32), 32,
                       None)],
            arena=FusionArena())

    def test_adopts_byte_compatible_buffers(self):
        import ml_dtypes
        plan = self._plan()
        bufs = [np.zeros(32, ml_dtypes.bfloat16),
                np.zeros(8, np.float32)]
        out = plan.adopt_packed(bufs)
        assert out is not None
        assert out[0] is bufs[0] and out[1] is bufs[1]

    def test_rejects_wrong_dtype_or_size(self):
        import ml_dtypes
        plan = self._plan()
        assert plan.adopt_packed(
            [np.zeros(32, np.float16), np.zeros(8, np.float32)]) is None
        assert plan.adopt_packed(
            [np.zeros(31, ml_dtypes.bfloat16),
             np.zeros(8, np.float32)]) is None
        assert plan.adopt_packed([np.zeros(32, ml_dtypes.bfloat16)]) \
            is None
        assert plan.adopt_packed(
            [None, np.zeros(8, np.float32)]) is None

    def test_makes_noncontiguous_contiguous(self):
        import ml_dtypes
        plan = self._plan()
        wide = np.zeros((32, 2), ml_dtypes.bfloat16)
        out = plan.adopt_packed([wide[:, 0], np.zeros(8, np.float32)])
        assert out is not None
        assert out[0].flags["C_CONTIGUOUS"]


# -- in-process IciPlane over the conftest-forced 8-device mesh -------------

def _plane(max_devices=0):
    jax = pytest.importorskip("jax")
    if len(jax.local_devices()) < 2:
        pytest.skip("needs the forced multi-device host platform")
    from horovod_tpu.ops.xla_ops import IciPlane
    p = IciPlane(max_devices)
    assert p.probe()
    return p


class TestIciPlane:
    @pytest.mark.parametrize("wire,out_np,n", [
        (wd.WIRE_NONE, np.float32, 1000),
        (wd.WIRE_BF16, "bfloat16", 1000),
        (wd.WIRE_FP16, np.float16, 777),
    ])
    def test_fused_pack_bit_exact_vs_host_pack(self, wire, out_np, n):
        import ml_dtypes
        p = _plane()
        rng = np.random.RandomState(7)
        flat = rng.randn(n).astype(np.float32)
        for prescale in (1.0, 0.5):
            got = p.fused_pack((0, 0b1, 0), flat, prescale, wire)
            ref = flat * np.float32(prescale) if prescale != 1.0 \
                else flat
            if wire:
                ref = ref.astype(
                    ml_dtypes.bfloat16 if out_np == "bfloat16"
                    else out_np)
            assert got.dtype == ref.dtype
            assert got.tobytes() == ref.tobytes()
            assert got.flags.writeable

    def test_compile_count_flat_across_replays(self):
        p = _plane()
        flat = np.arange(640, dtype=np.float32)
        p.fused_pack((0, 0b1, 0), flat, 1.0, wd.WIRE_BF16)
        c = p.compiles
        for _ in range(20):
            p.fused_pack((0, 0b1, 0), flat, 1.0, wd.WIRE_BF16)
        assert p.compiles == c
        assert p.cycles >= 21
        # a new signature compiles exactly once more
        p.fused_pack((0, 0b11, 1), flat, 1.0, wd.WIRE_BF16)
        assert p.compiles == c + 1

    def test_fused_reduce_partials_matches_wire_precision_sum(self):
        import ml_dtypes
        p = _plane()
        rng = np.random.RandomState(11)
        parts = rng.randn(p.ndev, 257).astype(np.float32)
        got = p.fused_reduce_partials((1, 0b1, 0), parts, 1.0,
                                      wd.WIRE_NONE)
        np.testing.assert_allclose(
            np.asarray(got, np.float64),
            parts.astype(np.float64).sum(axis=0), rtol=1e-5)
        # wire-precision semantics: rows cast to bf16 BEFORE the sum
        gotc = p.fused_reduce_partials((1, 0b1, 1), parts, 1.0,
                                       wd.WIRE_BF16)
        assert gotc.dtype == np.dtype(ml_dtypes.bfloat16)

    def test_epoch_bump_evicts_compiled_plans(self):
        p = _plane()
        flat = np.arange(64, dtype=np.float32)
        p.note_cache_epoch(0)
        p.fused_pack((0, 0b1, 0), flat, 1.0, wd.WIRE_NONE)
        assert p._cache
        p.note_cache_epoch(0)
        assert p._cache
        p.note_cache_epoch(1)
        assert not p._cache

    def test_declines_unsupported_payloads(self):
        import jax
        p = _plane()
        assert p.fused_pack((0, 1, 0), np.arange(8, dtype=np.int32),
                            1.0, wd.WIRE_NONE) is None
        if not jax.config.jax_enable_x64:
            # f64 would be silently canonicalized to f32 on device —
            # never byte-compatible with the plan, so decline up front
            assert p.fused_pack(
                (0, 1, 0), np.arange(8, dtype=np.float64), 1.0,
                wd.WIRE_NONE) is None
        assert p.fused_pack((0, 1, 0),
                            np.arange(8, dtype=np.float32), 1.0,
                            wd.WIRE_INT8) is None
        assert p.fused_pack((0, 1, 0),
                            np.zeros(0, np.float32), 1.0,
                            wd.WIRE_NONE) is None

    def test_max_devices_caps_the_mesh(self):
        p = _plane(max_devices=2)
        assert p.ndev == 2
        flat = np.arange(11, dtype=np.float32)  # ragged over 2 shards
        got = p.fused_pack((0, 1, 0), flat, 1.0, wd.WIRE_NONE)
        assert got.tobytes() == flat.tobytes()


# -- multi-process legs -----------------------------------------------------

def test_ici_steady_engages_precompiled_plane():
    """ws=2 over forced 8-device meshes: steady cycles ride the
    fused-psum executable (ici_cycles advance, ici_compiles flat),
    ALG_ICI is provably stamped, and the Python side of the mesh leg
    performs zero fallback copies."""
    run_scenario("ici_steady", 2, timeout=150.0, extra_env=_ICI_ENV)


def test_ici_steady_compressed_bit_exact_vs_socket_replay(tmp_path):
    """The acceptance bit-exactness leg: a bf16-compressed ICI world
    and a fresh all-socket world replaying the same submissions must
    produce BYTE-IDENTICAL results — the on-device prescale+cast is
    the same function as the host pack."""
    ici = str(tmp_path / "ici.npy")
    sock = str(tmp_path / "sock.npy")
    run_scenario(
        "ici_steady", 2, timeout=150.0,
        extra_env={**_ICI_ENV, "HOROVOD_COMPRESSION": "bf16",
                   "HVD_ICI_OUT": ici})
    run_scenario(
        "ici_steady", 2, timeout=150.0,
        extra_env={**_SOCKET_ENV, "HOROVOD_TPU_METRICS": "1",
                   "HOROVOD_COMPRESSION": "bf16",
                   "HVD_ICI_EXPECT": "0", "HVD_ICI_OUT": sock})
    a = np.load(ici)
    b = np.load(sock)
    assert a.tobytes() == b.tobytes()


def test_ici_hetero_world_degrades_consistently(tmp_path):
    """One rank without a multi-device runtime (its XLA_FLAGS carry no
    forced device count): controller.agree() must turn the plane off
    WORLD-WIDE — zero ici cycles on every rank — and the degraded run
    stays bit-exact with an all-socket world."""
    mixed = str(tmp_path / "mixed.npy")
    plain = str(tmp_path / "plain.npy")
    run_scenario(
        "ici_steady", 3, timeout=150.0,
        extra_env={**_ICI_ENV, "HVD_ICI_EXPECT": "0",
                   "HVD_ICI_OUT": mixed},
        per_rank_env=lambda rank: (
            {"XLA_FLAGS": ""} if rank == 1 else {}))
    run_scenario(
        "ici_steady", 3, timeout=150.0,
        extra_env={**_SOCKET_ENV, "HOROVOD_TPU_METRICS": "1",
                   "HVD_ICI_EXPECT": "0", "HVD_ICI_OUT": plain})
    a = np.load(mixed)
    b = np.load(plain)
    assert a.tobytes() == b.tobytes()


def test_abort_sigkill_mid_ici_cycle():
    """SIGKILL rank 1 deep in ALG_ICI steady state: survivors must
    still raise WorldAbortedError naming the dead rank within the
    heartbeat deadline — the mesh leg cannot mask the PR 2 fail-fast
    invariant."""
    run_scenario(
        "abort_sigkill_ici_steady", 3, timeout=60.0,
        extra_env={**_HB_ENV, **_ICI_ENV,
                   "HOROVOD_FAULT_SPEC": "rank=1:kill:op=40"},
        expect_rc={1: _SIGKILL_RC})
