"""In-jit SPMD collectives over the virtual 8-device CPU mesh
(test model: reference test/test_tensorflow.py collective correctness
vs locally computed expectation, re-aimed at the mesh path)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu import spmd

from horovod_tpu.compat import jaxshim


@pytest.fixture(scope="module")
def mesh():
    return spmd.create_mesh({"data": 8})


def _shard_map(mesh, body, in_specs, out_specs):
    return jax.jit(jaxshim.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs))


def test_mesh_default_axes():
    m = spmd.create_mesh()
    assert m.axis_names == ("data",)
    assert m.devices.size == 8


def test_mesh_infer_axis():
    m = spmd.create_mesh({"data": -1, "model": 2})
    assert dict(zip(m.axis_names, m.devices.shape)) == {
        "data": 4, "model": 2}


def test_mesh_bad_sizes():
    with pytest.raises(ValueError):
        spmd.create_mesh({"data": 3})
    with pytest.raises(ValueError):
        spmd.create_mesh({"data": -1, "model": -1})


def test_allreduce_mean_sum(mesh):
    # Global (8, 2) sharded over 'data': each replica holds one (1, 2)
    # row; allreduce preserves the per-replica shape (hvd semantics).
    x = np.arange(16, dtype=np.float32).reshape(8, 2)
    f = _shard_map(mesh, lambda t: spmd.allreduce(t, op=spmd.Sum),
                   P("data"), P())
    np.testing.assert_allclose(np.asarray(f(x)), x.sum(0, keepdims=True))
    g = _shard_map(mesh, lambda t: spmd.allreduce(t, op=spmd.Average),
                   P("data"), P())
    np.testing.assert_allclose(np.asarray(g(x)), x.mean(0, keepdims=True))


def test_allreduce_min_max_scale(mesh):
    x = np.random.RandomState(0).randn(8, 3).astype(np.float32)
    fmin = _shard_map(mesh, lambda t: spmd.allreduce(t, op=spmd.Min),
                      P("data"), P())
    np.testing.assert_allclose(np.asarray(fmin(x)),
                               x.min(0, keepdims=True))
    fs = _shard_map(
        mesh, lambda t: spmd.allreduce(t, op=spmd.Sum,
                                       prescale_factor=2.0,
                                       postscale_factor=0.5),
        P("data"), P())
    np.testing.assert_allclose(np.asarray(fs(x)), x.sum(0, keepdims=True),
                               rtol=1e-6)


def test_allgather(mesh):
    x = np.arange(24, dtype=np.float32).reshape(8, 3)
    f = _shard_map(mesh, lambda t: spmd.allgather(t), P("data"), P())
    np.testing.assert_allclose(np.asarray(f(x)), x)


def test_broadcast(mesh):
    x = np.tile(np.arange(8, dtype=np.float32)[:, None], (1, 4))
    f = _shard_map(mesh, lambda t: spmd.broadcast(t, root_rank=3),
                   P("data"), P("data"))
    out = np.asarray(f(x))
    assert (out == 3.0).all()


def test_alltoall(mesh):
    # Each replica holds 8 rows = 8 one-row blocks; block d goes to
    # replica d. Globally that is a block transpose of the 8x8 grid.
    x = np.arange(128, dtype=np.float32).reshape(64, 2)
    f = _shard_map(mesh, lambda t: spmd.alltoall(t), P("data"), P("data"))
    expected = x.reshape(8, 8, 2).transpose(1, 0, 2).reshape(64, 2)
    np.testing.assert_allclose(np.asarray(f(x)), expected)


def test_reducescatter(mesh):
    # Each replica holds an (8, 3) tensor; the summed tensor is
    # scattered one row per replica → global output (8, 3) = blockwise
    # sum of the shards.
    x = np.random.RandomState(1).randn(64, 3).astype(np.float32)

    def body(t):
        return spmd.reducescatter(t, op=spmd.Sum)

    f = _shard_map(mesh, body, P("data"), P("data"))
    expected = x.reshape(8, 8, 3).sum(0)
    np.testing.assert_allclose(np.asarray(f(x)), expected, rtol=1e-5)


def test_allreduce_gradients_tree_with_compression(mesh):
    from horovod_tpu import Compression
    tree = {"a": np.full((8, 2), 2.0, np.float32),
            "b": np.ones((8, 4), np.float32)}

    def body(t):
        return spmd.allreduce_gradients(t, compression=Compression.bf16)

    f = _shard_map(mesh, body, P("data"), P())
    out = f(tree)
    np.testing.assert_allclose(np.asarray(out["a"]), [[2.0, 2.0]])
    assert out["a"].dtype == jnp.float32  # restored after wire cast


def test_broadcast_variables_tree(mesh):
    tree = {"w": np.tile(np.arange(8, dtype=np.float32)[:, None], (1, 2))}
    f = _shard_map(mesh, lambda t: spmd.broadcast_variables(t, 5),
                   P("data"), P("data"))
    assert (np.asarray(f(tree)["w"]) == 5.0).all()


def test_mesh_rank_size(mesh):
    f = _shard_map(
        mesh,
        lambda t: t * 0 + spmd.mesh_rank("data").astype(jnp.float32),
        P("data"), P("data"))
    out = np.asarray(f(np.zeros((8, 1), np.float32)))
    np.testing.assert_allclose(out[:, 0], np.arange(8))


def test_hierarchical_axes():
    # ('cross', 'local') two-level mesh: psum over both axes == global sum
    m = spmd.create_mesh({"cross": 2, "local": 4})
    x = np.arange(8, dtype=np.float32).reshape(2, 4)

    f = jax.jit(jaxshim.shard_map(
        lambda t: spmd.allreduce(t, op=spmd.Sum, axis=("cross", "local")),
        mesh=m, in_specs=P("cross", "local"), out_specs=P()))
    np.testing.assert_allclose(np.asarray(f(x)), x.sum())


def test_shard_batch_and_shardings(mesh):
    batch = {"x": np.zeros((16, 3), np.float32)}
    out = spmd.shard_batch(mesh, batch)
    assert out["x"].sharding.spec == P("data")


def test_create_hybrid_mesh_axis_order(monkeypatch):
    """DCN axes lead the mesh (outer/slower network outermost); ICI
    axes follow — the contract the hierarchical collectives assume.
    Real multi-slice construction needs multi-slice hardware, so the
    device grid is injected."""
    import numpy as np
    import jax
    from jax.experimental import mesh_utils
    from horovod_tpu import spmd

    captured = {}

    def fake_hybrid(ici_shape, dcn_mesh_shape):
        captured["ici"] = tuple(ici_shape)
        captured["dcn"] = tuple(dcn_mesh_shape)
        return np.array(jax.devices()[:8]).reshape(2, 2, 2)

    monkeypatch.setattr(mesh_utils, "create_hybrid_device_mesh",
                        fake_hybrid)
    mesh = spmd.create_hybrid_mesh({"seq": 2, "model": 2}, {"data": 2})
    assert captured == {"ici": (2, 2), "dcn": (2,)}
    assert mesh.axis_names == ("data", "seq", "model")
    assert dict(mesh.shape) == {"data": 2, "seq": 2, "model": 2}


def test_distributed_optimizer_predivide_and_compression(mesh):
    """hvd.jax.DistributedOptimizer: the default pmean path, the
    prescale/postscale pre-divide path, and the bf16-compressed path
    must all produce the mean-gradient SGD update (prescale by 1/f,
    postscale by f/n — net mean, smaller intermediates; reference:
    allreduce prescale/postscale contract)."""
    import optax
    import horovod_tpu.jax as hj

    rng = np.random.RandomState(3)
    params = {"w": rng.randn(4, 6).astype(np.float32)}
    g_stacked = rng.randn(8, 4, 6).astype(np.float32)
    want_g = g_stacked.mean(0)

    def run(tx):
        def step(p, g8):
            g = {"w": g8[0]}
            state = tx.init(p)
            updates, _ = tx.update(g, state, p)
            return optax.apply_updates(p, updates)
        f = _shard_map(mesh, step, (P(), P("data")), P())
        return np.asarray(f(params, g_stacked)["w"])

    want = params["w"] - 0.1 * want_g
    base = run(hj.DistributedOptimizer(optax.sgd(0.1)))
    np.testing.assert_allclose(base, want, rtol=1e-5)

    pre = run(hj.DistributedOptimizer(optax.sgd(0.1),
                                      gradient_predivide_factor=8.0))
    np.testing.assert_allclose(pre, want, rtol=1e-5)

    comp = run(hj.DistributedOptimizer(
        optax.sgd(0.1), compression=hj.Compression.bf16))
    np.testing.assert_allclose(comp, want, rtol=2e-2, atol=1e-2)


# -- the in-jit exchange under the backward (spmd/overlap.py) -------------

def _described_mesh(platform, **axes):
    """A stand-in for a mesh on devices this machine lacks: the options
    function reads the axes' sizes and the first device's platform."""
    import types
    shape = tuple(axes.values())
    devices = np.empty(shape, dtype=object)
    devices[...] = types.SimpleNamespace(platform=platform)
    return types.SimpleNamespace(shape=dict(axes), devices=devices)


@pytest.mark.parametrize("mesh_of,want", [
    (lambda: spmd.create_mesh({"data": 1}, devices=jax.devices()[:1]), None),
    (lambda: _described_mesh("tpu", data=1), None),
    # the CPU compiler refuses xla_tpu_* options: none off the TPU
    (lambda: spmd.create_mesh({"data": 4}, devices=jax.devices()[:4]), None),
    (lambda: _described_mesh("tpu", data=4), dict),
    (lambda: _described_mesh("tpu", data=2, model=2), dict),
    (lambda: _described_mesh("tpu", data=1, model=4), None),
], ids=["cpu-1", "tpu-1", "cpu-4", "tpu-4", "tpu-2x2", "tpu-model-only"])
def test_overlap_options_follow_the_mesh(mesh_of, want):
    got = spmd.overlap_compiler_options(mesh_of(), "data")
    if want is None:
        assert got is None
    else:
        assert isinstance(got, dict) and got
        assert got["xla_enable_async_all_reduce"] is True
        assert all(k.startswith("xla_") for k in got)
        # a fresh dictionary each time: a caller may add to it
        got["mine"] = 1
        assert "mine" not in spmd.overlap_compiler_options(
            _described_mesh("tpu", data=4))


def test_overlap_options_axes():
    mesh = _described_mesh("tpu", data=2, model=2)
    assert spmd.overlap_compiler_options(mesh, ("data", "model"))
    with pytest.raises(ValueError, match="no axis 'rows'"):
        spmd.overlap_compiler_options(mesh, "rows")
    with pytest.raises(ValueError, match="no axis 'rows'"):
        spmd.overlap_compiler_options(mesh, ("data", "rows"))


_SCHEDULED = """
HloModule jit_step, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%fused_computation.7 (p: f32[256,512]) -> (f32[256,512], u32[]) {
  %p = f32[256,512]{1,0} parameter(0)
  %all-reduce.4 = f32[256,512]{1,0} all-reduce(%p), channel_id=1, to_apply=%add
  ROOT %custom-call.1 = (f32[256,512]{1,0}, u32[]{:S(2)}) custom-call(%all-reduce.4), custom_call_target="AsyncCollectiveStart"
}

%fused_computation.8 (p: f32[64]) -> (f32[64], u32[]) {
  %p = f32[64]{0} parameter(0)
  %all-reduce.5 = f32[64]{0} all-reduce(%p), channel_id=2, to_apply=%add
  ROOT %custom-call.2 = (f32[64]{0}, u32[]{:S(2)}) custom-call(%all-reduce.5), custom_call_target="AsyncCollectiveStart"
}

%fused_computation.9 (p: bf16[64]) -> (bf16[256], u32[]) {
  %p = bf16[64]{0} parameter(0)
  %all-gather.1 = bf16[256]{0} all-gather(%p), channel_id=3, dimensions={0}
  ROOT %custom-call.3 = (bf16[256]{0}, u32[]{:S(2)}) custom-call(%all-gather.1), custom_call_target="AsyncCollectiveStart"
}

ENTRY %main (x: f32[256,512], y: f32[64], z: bf16[64]) -> f32[256,512] {
  %x = f32[256,512]{1,0:T(8,128)} parameter(0)
  %y = f32[64]{0:T(64)} parameter(1)
  %z = bf16[64]{0} parameter(2)
  %async-collective-start.1 = (f32[256,512]{1,0:T(8,128)}, u32[]{:S(2)}) fusion(%x), kind=kCustom, calls=%fused_computation.7
  %get-tuple-element.1 = f32[256,512]{1,0:T(8,128)} get-tuple-element(%async-collective-start.1), index=0
  %get-tuple-element.2 = u32[]{:S(2)} get-tuple-element(%async-collective-start.1), index=1
  %fusion.3 = (f32[256,512]{1,0:T(8,128)}, u32[]{:S(2)}) fusion(%x, %get-tuple-element.2), kind=kOutput, calls=%fused_computation.1
  %custom-call.9 = f32[8]{0} custom-call(%y), custom_call_target="ConcatBitcast"
  %flash_fwd.2 = f32[256,512]{1,0} custom-call(%x), custom_call_target="tpu_custom_call"
  %get-tuple-element.3 = u32[]{:S(2)} get-tuple-element(%fusion.3), index=1
  %async-collective-done.1 = f32[256,512]{1,0:T(8,128)} fusion(%get-tuple-element.1, %get-tuple-element.3), kind=kCustom, calls=%fused_computation.2
  %async-collective-start.2 = (f32[64]{0:T(64)}, u32[]{:S(2)}) fusion(%y), kind=kCustom, calls=%fused_computation.8
  %get-tuple-element.4 = f32[64]{0:T(64)} get-tuple-element(%async-collective-start.2), index=0
  %get-tuple-element.5 = u32[]{:S(2)} get-tuple-element(%async-collective-start.2), index=1
  %async-collective-done.2 = f32[64]{0:T(64)} fusion(%get-tuple-element.4, %get-tuple-element.5), kind=kCustom, calls=%fused_computation.3
  %async-collective-start.3 = (bf16[256]{0}, u32[]{:S(2)}) fusion(%z), kind=kCustom, calls=%fused_computation.9
  %get-tuple-element.6 = bf16[256]{0} get-tuple-element(%async-collective-start.3), index=0
  %fusion.5 = f32[64]{0} fusion(%async-collective-done.2), kind=kLoop, calls=%fused_computation.4
  %async-collective-done.3 = bf16[256]{0} fusion(%get-tuple-element.6), kind=kCustom, calls=%fused_computation.5
  %psum.12 = f32[2048,8]{1,0:T(8,128)} all-reduce(%fusion.5), channel_id=4, replica_groups={{0,1,2,3}}, to_apply=%add
  %all-reduce.13 = (f32[64]{0}, f32[2,2]{1,0}) all-reduce(%y, %fusion.5), channel_id=5, to_apply=%add
  %all-reduce-start.1 = f32[128]{0} all-reduce-start(%y), channel_id=6, to_apply=%add
  %dot.1 = f32[128]{0} dot(%y, %y), lhs_contracting_dims={0}, rhs_contracting_dims={0}
  %all-reduce-done.1 = f32[128]{0} all-reduce-done(%all-reduce-start.1)
  ROOT %out = f32[256,512]{1,0} add(%async-collective-done.1, %x)
}
"""


def test_collective_schedule_counts_all_reduces_by_kind():
    """A scheduled module as the TPU compiler prints one: an
    asynchronous pair carried by a fusion with a kernel beside it, a
    pair with nothing between, an all-gather's pair (not counted), two
    synchronous all-reduces (one combined), and a plain
    all-reduce-start/-done."""
    got = spmd.collective_schedule(_SCHEDULED)
    assert got["sync"] == {"count": 2,
                           "bytes": 2048 * 8 * 4 + (64 + 4) * 4}
    pairs = {p["name"]: p for p in got["pairs"]}
    assert set(pairs) == {"async-collective-start.1",
                          "async-collective-start.2", "all-reduce-start.1"}
    # the fusion and the Pallas kernel count, the ConcatBitcast does not
    assert pairs["async-collective-start.1"] == {
        "name": "async-collective-start.1", "bytes": 256 * 512 * 4,
        "ops_between": 2}
    assert pairs["async-collective-start.2"]["ops_between"] == 0
    assert pairs["async-collective-start.2"]["bytes"] == 64 * 4
    assert pairs["all-reduce-start.1"]["ops_between"] == 1
    assert got["async"] == {
        "count": 3, "bytes": 256 * 512 * 4 + 64 * 4 + 128 * 4,
        "overlapped": {"count": 2, "bytes": 256 * 512 * 4 + 128 * 4}}


def test_lm_train_step_trains_over_four_cpu_devices():
    """The step takes its compiler options from the mesh: over four
    forced CPU devices it gets none (the CPU compiler would refuse
    them), compiles, and trains; its one executable reduces the
    gradients, which ``collective_schedule`` finds in it."""
    from horovod_tpu.models import train_steps
    from horovod_tpu.models.transformer import (
        TransformerConfig, TransformerLM)
    mesh = spmd.create_mesh({"data": 4}, devices=jax.devices()[:4])
    model = TransformerLM(TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
        max_seq_len=16, dtype=jnp.float32))
    tx = train_steps.distributed_sgd()
    tokens = train_steps.synthetic_tokens(0, 8, 16, 64, mesh)
    rep = spmd.replicated_sharding(mesh)
    params = jax.jit(model.init, out_shardings=rep)(
        jax.random.key(0), tokens)["params"]
    opt = jax.jit(tx.init, out_shardings=rep)(params)
    step = train_steps.lm_train_step(model, tx, mesh)
    compiled = step.lower(params, opt, tokens).compile()
    sched = spmd.collective_schedule(compiled)
    n_bytes = sum(p.size * 4 for p in jax.tree_util.tree_leaves(params))
    assert (sched["sync"]["bytes"] + sched["async"]["bytes"]
            >= n_bytes)      # every leaf's gradient, and the loss
    losses = []
    for _ in range(4):
        params, opt, loss = compiled(params, opt, tokens)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    for leaf in jax.tree_util.tree_leaves(params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert all((s == shards[0]).all() for s in shards)
