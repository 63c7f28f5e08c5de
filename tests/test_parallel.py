"""Parallelism extensions: ring attention exactness, TP sharding rules,
and the composed dp x tp (x sp) Trainer on the 8-device CPU mesh."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from horovod_tpu import spmd
from horovod_tpu.models.transformer import (
    TransformerConfig, TransformerLM, causal_attention,
)
from horovod_tpu.compat import jaxshim
from horovod_tpu.parallel import (
    Trainer, TrainerConfig, infer_sharding, make_ring_attention,
    ring_attention, transformer_tp_rules,
)

pytestmark = pytest.mark.interpreter_of_its_own


def test_ring_attention_matches_reference():
    """Sequence sharded over 4 devices must reproduce single-device
    causal attention to fp32 tolerance."""
    mesh = spmd.create_mesh({"seq": 4}, devices=jax.devices()[:4])
    b, s, h, d = 2, 16, 2, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    expected = causal_attention(q, k, v)

    f = jax.jit(jaxshim.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis="seq"),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq")))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5)


def test_ring_attention_single_shard_degenerates():
    mesh = spmd.create_mesh({"seq": 1}, devices=jax.devices()[:1])
    b, s, h, d = 1, 8, 1, 4
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    f = jax.jit(jaxshim.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis="seq"),
        mesh=mesh, in_specs=(P(),) * 3, out_specs=P()))
    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               np.asarray(causal_attention(q, k, v)),
                               atol=2e-5)


def test_tp_rules_match_expected_paths():
    cfg = TransformerConfig(vocab_size=64, num_layers=1, num_heads=4,
                            head_dim=4, dtype=jnp.float32)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), tokens)
    mesh = spmd.create_mesh({"data": 4, "model": 2})
    shardings = infer_sharding(params, transformer_tp_rules("model"), mesh)
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): s
            for path, s in
            jax.tree_util.tree_flatten_with_path(shardings)[0]}
    qk = [k for k in flat if k.endswith("attn/q/kernel")][0]
    assert flat[qk].spec == P(None, "model", None)
    up = [k for k in flat if k.endswith("mlp/up/kernel")][0]
    assert flat[up].spec == P(None, "model")
    ln = [k for k in flat if "ln1/scale" in k][0]
    assert flat[ln].spec == P()


def _tiny_cfg(attention_fn=None):
    return TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                             head_dim=8, max_seq_len=16,
                             dtype=jnp.float32, attention_fn=attention_fn)


def test_trainer_dp_tp_step_runs_and_improves():
    import optax
    mesh = spmd.create_mesh({"data": 4, "model": 2})
    model = TransformerLM(_tiny_cfg())
    trainer = Trainer(model, mesh, optax.adam(1e-2),
                      TrainerConfig(data_axis="data", model_axis="model"))
    tokens = np.tile(np.arange(16, dtype=np.int32)[None], (8, 1))
    batch = {"tokens": tokens}
    state = trainer.init(jax.random.key(0), batch)
    losses = []
    for _ in range(5):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_a_trainers_step_compiles_once():
    """Two calls of the step are one executable: ``init`` commits the
    whole state to the mesh, the counter too, and the step hands the
    state back under the shardings it took."""
    import optax
    mesh = spmd.create_mesh({"data": 4, "model": 2})
    trainer = Trainer(TransformerLM(_tiny_cfg()), mesh, optax.adam(1e-2),
                      TrainerConfig(data_axis="data", model_axis="model"))
    batch = {"tokens": np.tile(np.arange(16, dtype=np.int32)[None], (8, 1))}
    state = trainer.init(jax.random.key(0), batch)
    took = jax.tree_util.tree_map(lambda a: (a.committed, a.sharding), state)
    for _ in range(2):
        state, _ = trainer.train_step(state, batch)
    assert trainer.step_fn()._cache_size() == 1
    assert all(committed for committed, _ in jax.tree_util.tree_leaves(
        took, is_leaf=lambda x: isinstance(x, tuple)))
    assert jax.tree_util.tree_map(
        lambda a: (a.committed, a.sharding), state) == took


def test_trainer_dp_tp_sp_with_ring_attention():
    import optax
    mesh = spmd.create_mesh({"data": 2, "seq": 2, "model": 2})
    attn = make_ring_attention(mesh, data_axis="data", seq_axis="seq",
                               model_axis="model")
    model = TransformerLM(_tiny_cfg(attention_fn=attn))
    trainer = Trainer(model, mesh, optax.sgd(1e-2),
                      TrainerConfig(data_axis="data", model_axis="model",
                                    seq_axis="seq"))
    tokens = np.tile(np.arange(16, dtype=np.int32)[None], (4, 1))
    batch = {"tokens": tokens}
    state = trainer.init(jax.random.key(0), batch)
    state, loss0 = trainer.train_step(state, batch)
    state, loss1 = trainer.train_step(state, batch)
    assert np.isfinite(loss0) and np.isfinite(loss1)
    assert float(loss1) < float(loss0)


def _first_loss_over_seq(attention_fn=None):
    """The first step's loss of the tiny model over ``data`` 2 x ``seq``
    4, with ``attention_fn(mesh)`` for its attention (dense without)."""
    import optax
    mesh = spmd.create_mesh({"data": 2, "seq": 4})
    attn = attention_fn and attention_fn(mesh)
    tokens = np.tile(np.arange(16, dtype=np.int32)[None], (4, 1))
    batch = {"tokens": tokens}
    trainer = Trainer(TransformerLM(_tiny_cfg(attention_fn=attn)), mesh,
                      optax.sgd(1e-2),
                      TrainerConfig(model_axis=None, seq_axis="seq"))
    _, loss = trainer.train_step(trainer.init(jax.random.key(7), batch),
                                 batch)
    return float(loss)


@pytest.fixture(scope="module")
def dense_loss_over_seq():
    """The dense trainer both sequence-parallel attentions are held to,
    built and compiled once."""
    return _first_loss_over_seq()


def test_sp_matches_dense_attention_loss(dense_loss_over_seq):
    """Loss with ring attention == loss with dense attention."""
    ringy = _first_loss_over_seq(lambda mesh: make_ring_attention(
        mesh, data_axis="data", seq_axis="seq", model_axis=None))
    np.testing.assert_allclose(dense_loss_over_seq, ringy, rtol=1e-4)


# ---------------------------------------------------------------------------
# pallas flash attention (interpret mode on CPU)
# ---------------------------------------------------------------------------

def test_flash_attention_matches_dense():
    from horovod_tpu.parallel.flash_attention import flash_attention
    rng = np.random.RandomState(3)
    b, s, h, d = 2, 128, 2, 32
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    ref = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_flash_attention_offsets_match_ring_semantics():
    """With q_offset/k_offset the kernel must reproduce the masked
    cross-block attention ring attention needs: a kv block entirely in
    the past attends fully; entirely in the future contributes zero."""
    from horovod_tpu.parallel.flash_attention import flash_attention
    rng = np.random.RandomState(4)
    b, s, h, d = 1, 64, 1, 16
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    # q block at global [64,128), kv block at [0,64): fully visible
    out = flash_attention(q, k, v, causal=True, q_offset=64, k_offset=0,
                          block_q=32, block_k=32, interpret=True)
    # equivalent dense: no mask at all (all k_pos < q_pos)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    probs = jax.nn.softmax(logits, axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)

    # kv block fully in the future: all masked -> zeros (guarded denom)
    out = flash_attention(q, k, v, causal=True, q_offset=0, k_offset=64,
                          block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_flash_attention_indivisible_falls_back():
    from horovod_tpu.parallel.flash_attention import flash_attention
    rng = np.random.RandomState(5)
    b, s, h, d = 1, 50, 1, 8  # 50 not divisible by any pow2 block
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    ref = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_ring_attention_flash_path_matches_dense():
    """Forced flash path (pallas interpret on CPU): forward and grad
    must match dense causal attention exactly."""
    from functools import partial
    mesh = spmd.create_mesh({"seq": 4}, devices=jax.devices()[:4])
    b, s, h, d = 1, 64, 2, 16
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    f = jax.jit(jaxshim.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis="seq",
                                       use_flash=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq")))
    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               np.asarray(causal_attention(q, k, v)),
                               atol=2e-5)
    g1 = jax.jit(jax.grad(lambda q, k, v: (f(q, k, v) ** 2).sum(),
                          argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(
        lambda q, k, v: (causal_attention(q, k, v) ** 2).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-4)


def test_flash_attention_grad_matches_dense():
    """The pallas backward kernels (dq / dk+dv) must reproduce dense
    causal-attention gradients — no O(S²) recompute fallback anymore."""
    from horovod_tpu.parallel.flash_attention import flash_attention
    rng = np.random.RandomState(11)
    b, s, h, d = 2, 64, 2, 16
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=32,
                              block_k=32, interpret=True)
        return (out ** 2).sum()

    def loss_dense(q, k, v):
        return (causal_attention(q, k, v) ** 2).sum()

    g1 = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-4)


def test_flash_attention_grad_noncausal_and_offsets():
    from horovod_tpu.parallel.flash_attention import flash_attention
    rng = np.random.RandomState(12)
    b, s, h, d = 1, 64, 1, 8
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    def dense_nc(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    def grads(attention):
        """Of the summed square of ``attention(q, k, v)``, one program."""
        return jax.jit(jax.grad(lambda *a: (attention(*a) ** 2).sum(),
                                argnums=(0, 1, 2)))(q, k, v)

    # non-causal
    g1 = grads(lambda *a: flash_attention(
        *a, causal=False, block_q=32, block_k=32, interpret=True))
    g2 = grads(dense_nc)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-4)

    # causal with a fully-past kv block (ring step shape): same as
    # non-causal dense
    g1 = grads(lambda *a: flash_attention(
        *a, causal=True, q_offset=64, k_offset=0, block_q=32,
        block_k=32, interpret=True))
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-4)

    # fully-future kv block: zero output -> zero grads, no NaN from
    # dead rows (l == 0)
    g1 = grads(lambda *a: flash_attention(
        *a, causal=True, q_offset=0, k_offset=64, block_q=32,
        block_k=32, interpret=True))
    for a in g1:
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), 0.0, atol=1e-6)


def test_ring_attention_flash_noncausal():
    """use_flash=True with causal=False must compute NON-causal
    attention (was: silently causal)."""
    mesh = spmd.create_mesh({"seq": 4}, devices=jax.devices()[:4])
    b, s, h, d = 1, 64, 1, 8
    rng = np.random.RandomState(13)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    f = jax.jit(jaxshim.shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=False,
                                       axis="seq", use_flash=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq")))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                               atol=2e-5)


def test_flash_attention_stats_values():
    from horovod_tpu.parallel.flash_attention import flash_attention_stats
    rng = np.random.RandomState(8)
    b, s, h, d = 1, 64, 1, 8
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    o, m, l = flash_attention_stats(q, k, v, causal=True, block_q=32,
                                    block_k=32, interpret=True)
    logits = np.einsum("bqhd,bkhd->bhqk", np.asarray(q),
                       np.asarray(k)) / np.sqrt(d)
    mask = np.tril(np.ones((s, s), bool))
    logits = np.where(mask[None, None], logits, -1e30)
    m_ref = logits.max(-1)
    l_ref = np.exp(logits - m_ref[..., None]).sum(-1)
    np.testing.assert_allclose(np.asarray(m), m_ref, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l), l_ref, rtol=1e-5)


# (id, q_offset, k_offset): a 512-row q shard against a 512-row kv shard,
# square tiles as the ring passes them, the ladder's sub-tile inside
_SHARD_CASES = [
    ("diagonal", 512, 512),
    ("wholly-past", 1024, 0),       # every sub-tile mask-free
    ("wholly-future", 0, 1024),     # no sub-tile computed
    ("one-row-sees-one-column", 0, 511),
    ("off-the-subtile-grid", 300, 77),
]


@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "noncausal"])
def test_flash_shards_traced_offsets_one_compile(causal):
    """The ring's use of the kernels: `flash_attention_stats` and
    `flash_attention_bwd` on traced offsets, every shard position
    through one compilation, forward and gradients against the dense
    reference. Without the mask the offsets change nothing."""
    from horovod_tpu.parallel import flash_attention as fa
    rng = np.random.RandomState(21)
    b, s, h, d = 1, 512, 2, 16
    q, k, v, g = (jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
                  for _ in range(4))
    assert fa._subtile_for(d, 512, 512) != (512, 512)

    @jax.jit
    def shard(q_off, k_off):
        o, m, l = fa.flash_attention_stats(
            q, k, v, causal=causal, q_offset=q_off, k_offset=k_off,
            block_q=512, block_k=512, interpret=True)
        return (o,) + fa.flash_attention_bwd(
            q, k, v, o, m, l, g, causal=causal, q_offset=q_off,
            k_offset=k_off, block_q=512, block_k=512, interpret=True)

    for name, q_off, k_off in _SHARD_CASES:
        ref, vjp = jax.vjp(
            lambda q, k, v: fa._dense_reference(q, k, v, causal, q_off,
                                                k_off), q, k, v)
        got = shard(jnp.int32(q_off), jnp.int32(k_off))
        for a, b_ in zip(got, (ref,) + vjp(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-5, err_msg=name)
    assert shard._cache_size() == 1


def test_flash_subtile_gauge_reaches_the_registry(monkeypatch):
    """A world with its metrics plane on reads how often the causal
    structure engaged in the flash call traced last; traced offsets
    (the ring's) and a plane that is off write nothing."""
    import horovod_tpu as hvd
    from horovod_tpu.parallel import flash_attention as fa
    rng = np.random.RandomState(22)
    q, k, v = (jnp.asarray(rng.randn(1, 256, 1, 16), jnp.float32)
               for _ in range(3))

    def call(q_off=0):
        return fa.flash_attention(q, k, v, causal=True, q_offset=q_off,
                                  block_q=128, block_k=256, interpret=True)

    def gauges():
        return {name: rec["v"]
                for name, rec in hvd.metrics()["local"].items()
                if name.startswith("hvd_flash_subtiles")}

    hvd.shutdown()
    call()                                  # no world: nothing to write to
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    hvd.init()
    try:
        assert gauges() == {}
        jax.jit(call)(jnp.int32(0))         # traced offset: no count
        assert gauges() == {}
        call()
        want = fa.causal_subtile_counts(
            256, 256, 128, 256, fa._subtile_for(16, 128, 256))
        assert gauges() == {
            f'hvd_flash_subtiles{{kind="{kind}"}}': float(n)
            for kind, n in want.items()}
    finally:
        hvd.shutdown()


# ---------------------------------------------------------------------------
# Expert parallelism (MoE)
# ---------------------------------------------------------------------------

def test_moe_matches_per_token_reference():
    """MoEMLP's dispatch/combine einsums == routing each token through
    its argmax expert directly (capacity ample, nothing dropped)."""
    from horovod_tpu.models.transformer import MoEMLP, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                            head_dim=4, mlp_ratio=2, dtype=jnp.float32,
                            num_experts=4, expert_capacity_factor=4.0)
    layer = MoEMLP(cfg)
    x = jax.random.normal(jax.random.key(0), (2, 8, cfg.embed_dim),
                          jnp.float32)
    variables = jax.jit(layer.init)(jax.random.key(1), x)
    y = jax.jit(layer.apply)(variables, x)

    p = variables["params"]
    wr = np.asarray(p["router"]["kernel"], np.float64)
    w1 = np.asarray(p["w1"], np.float64)
    w2 = np.asarray(p["w2"], np.float64)
    xt = np.asarray(x, np.float64).reshape(-1, cfg.embed_dim)
    logits = xt @ wr
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    idx = probs.argmax(-1)
    ref = np.zeros_like(xt)
    gelu = lambda v: 0.5 * v * (1 + np.tanh(
        np.sqrt(2 / np.pi) * (v + 0.044715 * v ** 3)))
    for n in range(xt.shape[0]):
        e = idx[n]
        ref[n] = probs[n, e] * (gelu(xt[n] @ w1[e]) @ w2[e])
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.embed_dim),
                               ref, rtol=2e-4, atol=2e-5)


def test_moe_capacity_drops_overflow_tokens():
    """With capacity 1 and every token routed to one expert, only the
    first token per expert survives; the rest combine to zero."""
    from horovod_tpu.models.transformer import MoEMLP, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                            head_dim=4, mlp_ratio=2, dtype=jnp.float32,
                            num_experts=2,
                            expert_capacity_factor=2 / 8.0)  # C = 1
    layer = MoEMLP(cfg)
    x = jnp.tile(jax.random.normal(jax.random.key(0),
                                   (1, 1, cfg.embed_dim)), (1, 4, 1))
    variables = jax.jit(layer.init)(jax.random.key(1), x)
    y = np.asarray(jax.jit(layer.apply)(variables, x))[0]
    # identical tokens -> same expert; capacity 1 keeps only token 0
    assert np.any(y[0] != 0.0)
    np.testing.assert_allclose(y[1:], 0.0)


def test_trainer_dp_tp_ep_step_runs_and_shards_experts():
    """dp x tp x ep on the 8-device CPU mesh: expert weights sharded
    over the expert axis (composed with the per-expert Megatron split),
    the step runs, and the loss improves."""
    import optax
    from horovod_tpu.models.transformer import TransformerConfig

    mesh = spmd.create_mesh({"data": 2, "expert": 2, "model": 2})
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            head_dim=8, max_seq_len=16,
                            dtype=jnp.float32, num_experts=2,
                            moe_every=2)
    trainer = Trainer(TransformerLM(cfg), mesh, optax.adam(1e-2),
                      TrainerConfig(data_axis="data", model_axis="model",
                                    expert_axis="expert"))
    tokens = np.tile(np.arange(16, dtype=np.int32)[None], (8, 1))
    batch = {"tokens": tokens}
    state = trainer.init(jax.random.key(0), batch)

    moe_params = state["params"]["params"]["block_1"]["moe"]
    w1_sharding = moe_params["w1"].sharding
    assert w1_sharding.spec == P("expert", None, "model"), w1_sharding
    router_sharding = moe_params["router"]["kernel"].sharding
    assert router_sharding.spec == P(), router_sharding

    losses = []
    for _ in range(5):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_moe_aux_loss_sowed():
    from horovod_tpu.models.transformer import (
        TransformerConfig, TransformerLM, moe_aux_loss,
    )
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            head_dim=4, dtype=jnp.float32,
                            num_experts=2, moe_every=2)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, 8), jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(0), tokens)
    _, inter = jax.jit(partial(
        model.apply, mutable=["intermediates"]))(variables, tokens)
    aux = moe_aux_loss(inter["intermediates"])
    # perfectly balanced routing gives aux == 1.0; anything routed
    # gives a finite positive value >= 1 for top-1 switch gating
    assert float(aux) >= 1.0 - 1e-3


def test_ep_without_tp_still_shards_experts():
    """expert_axis without model_axis must still emit expert rules
    (PartitionSpec treats the absent model split as replicated)."""
    import optax
    from horovod_tpu.models.transformer import TransformerConfig

    mesh = spmd.create_mesh({"data": 2, "expert": 4})
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            head_dim=4, dtype=jnp.float32,
                            num_experts=4, moe_every=2)
    trainer = Trainer(TransformerLM(cfg), mesh, optax.sgd(1e-2),
                      TrainerConfig(data_axis="data", model_axis=None,
                                    expert_axis="expert"))
    batch = {"tokens": np.tile(np.arange(8, dtype=np.int32)[None],
                               (4, 1))}
    state = trainer.init(jax.random.key(0), batch)
    w1 = state["params"]["params"]["block_1"]["moe"]["w1"]
    assert w1.sharding.spec == P("expert", None, None), w1.sharding
    state, loss = trainer.train_step(state, batch)
    assert np.isfinite(float(loss))


def test_indivisible_expert_axis_fails_with_clear_error():
    """An expert axis larger than num_experts must fail at init with an
    actionable message, not a deep device_put error."""
    import optax
    import pytest as _pytest
    from horovod_tpu.models.transformer import TransformerConfig

    mesh = spmd.create_mesh({"data": 1, "expert": 8})
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            head_dim=4, dtype=jnp.float32,
                            num_experts=2, moe_every=2)
    trainer = Trainer(TransformerLM(cfg), mesh, optax.sgd(1e-2),
                      TrainerConfig(data_axis="data", model_axis=None,
                                    expert_axis="expert"))
    batch = {"tokens": np.zeros((1, 8), np.int32)}
    with _pytest.raises(ValueError, match="num_experts"):
        trainer.init(jax.random.key(0), batch)


# ---------------------------------------------------------------------------
# Pipeline parallelism (GPipe over a mesh axis)
# ---------------------------------------------------------------------------

def _pp_block(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _pp_setup(n_stages, d=8):
    rng = np.random.RandomState(0)
    stacked = {
        "w": jnp.asarray(rng.randn(n_stages, d, d) * 0.5, jnp.float32),
        "b": jnp.asarray(rng.randn(n_stages, d) * 0.1, jnp.float32),
    }
    x = jnp.asarray(rng.randn(8, d), jnp.float32)
    return stacked, x


@jax.jit
def _pp_sequential(stacked, x):
    for s in range(stacked["w"].shape[0]):
        x = _pp_block({"w": stacked["w"][s], "b": stacked["b"][s]}, x)
    return x


@pytest.mark.parametrize("num_microbatches", [2, 4, 8])
def test_pipeline_matches_sequential(num_microbatches):
    """4 pipeline stages over 4 devices == running the 4 blocks
    sequentially, for any microbatch count."""
    from horovod_tpu.parallel import make_pipeline_apply
    mesh = spmd.create_mesh({"stage": 4}, devices=jax.devices()[:4])
    stacked, x = _pp_setup(4)
    run = make_pipeline_apply(mesh, _pp_block,
                              num_microbatches=num_microbatches)
    out = run(stacked, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_pp_sequential(stacked, x)),
                               atol=1e-5)


def test_pipeline_gradients_match_sequential():
    """Autodiff through the scan + ppermute schedule reproduces the
    sequential gradients (the backward schedule comes for free)."""
    from horovod_tpu.parallel import make_pipeline_apply
    mesh = spmd.create_mesh({"stage": 4}, devices=jax.devices()[:4])
    stacked, x = _pp_setup(4)

    run = make_pipeline_apply(mesh, _pp_block, num_microbatches=4)

    def pipe_loss(p):
        return jnp.mean(run(p, x) ** 2)

    def seq_loss(p):
        return jnp.mean(_pp_sequential(p, x) ** 2)

    gp = jax.jit(jax.grad(pipe_loss))(stacked)
    gs = jax.jit(jax.grad(seq_loss))(stacked)
    np.testing.assert_allclose(np.asarray(gp["w"]), np.asarray(gs["w"]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(gp["b"]), np.asarray(gs["b"]),
                               atol=1e-5)


def test_pipeline_transformer_blocks():
    """Pipeline the transformer's homogeneous block tower: 2 stages x
    identical Block params == sequential block application."""
    from horovod_tpu.parallel import make_pipeline_apply
    from horovod_tpu.models.transformer import Block

    cfg = _tiny_cfg()
    mesh = spmd.create_mesh({"stage": 2}, devices=jax.devices()[:2])
    block = Block(cfg)
    x = jnp.asarray(np.random.RandomState(1).randn(4, 16, cfg.embed_dim),
                    jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32)[None],
                                 (4, 16))
    init = jax.jit(block.init)
    p0 = init(jax.random.key(0), x, positions)["params"]
    p1 = init(jax.random.key(1), x, positions)["params"]
    stacked = jax.tree_util.tree_map(
        lambda a, b: jnp.stack([a, b]), p0, p1)

    def block_fn(params, h):
        # positions derived per microbatch (batch-size agnostic)
        pos = jnp.broadcast_to(
            jnp.arange(h.shape[1], dtype=jnp.int32)[None], h.shape[:2])
        return block.apply({"params": params}, h, pos)

    run = make_pipeline_apply(mesh, block_fn, num_microbatches=2)
    out = run(stacked, x)
    ref = jax.jit(lambda h: block_fn(p1, block_fn(p0, h)))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)


def test_pipeline_composes_with_data_parallelism():
    """dp x pp on the 8-device mesh (data=2, stage=4): forward and
    gradients match the sequential single-device reference; the
    gradient all-reduce over the data axis comes from shard_map's
    transpose, no manual psum."""
    from horovod_tpu.parallel import make_pipeline_apply
    mesh = spmd.create_mesh({"data": 2, "stage": 4})
    stacked, x = _pp_setup(4)

    run = make_pipeline_apply(mesh, _pp_block, num_microbatches=2,
                              data_axis="data")
    np.testing.assert_allclose(np.asarray(run(stacked, x)),
                               np.asarray(_pp_sequential(stacked, x)),
                               atol=1e-5)

    gp = jax.jit(jax.grad(lambda p: jnp.mean(run(p, x) ** 2)))(stacked)
    gs = jax.jit(jax.grad(
        lambda p: jnp.mean(_pp_sequential(p, x) ** 2)))(stacked)
    np.testing.assert_allclose(np.asarray(gp["w"]), np.asarray(gs["w"]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(gp["b"]), np.asarray(gs["b"]),
                               atol=1e-5)


def test_moe_top2_matches_per_token_reference():
    """Top-2 gating: each token's output is the gate-weighted sum of
    its two best experts' FFNs with gates renormalized over the pair
    (capacity ample, nothing dropped)."""
    from horovod_tpu.models.transformer import MoEMLP, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                            head_dim=4, mlp_ratio=2, dtype=jnp.float32,
                            num_experts=4, moe_top_k=2,
                            expert_capacity_factor=8.0)
    layer = MoEMLP(cfg)
    x = jax.random.normal(jax.random.key(2), (2, 8, cfg.embed_dim),
                          jnp.float32)
    variables = jax.jit(layer.init)(jax.random.key(3), x)
    y = jax.jit(layer.apply)(variables, x)

    p = variables["params"]
    wr = np.asarray(p["router"]["kernel"], np.float64)
    w1 = np.asarray(p["w1"], np.float64)
    w2 = np.asarray(p["w2"], np.float64)
    xt = np.asarray(x, np.float64).reshape(-1, cfg.embed_dim)
    logits = xt @ wr
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    gelu = lambda v: 0.5 * v * (1 + np.tanh(
        np.sqrt(2 / np.pi) * (v + 0.044715 * v ** 3)))
    ref = np.zeros_like(xt)
    for n in range(xt.shape[0]):
        order = np.argsort(-probs[n])
        e1, e2 = order[0], order[1]
        g1, g2 = probs[n, e1], probs[n, e2]
        z = g1 + g2
        ref[n] = (g1 / z) * (gelu(xt[n] @ w1[e1]) @ w2[e1]) \
            + (g2 / z) * (gelu(xt[n] @ w1[e2]) @ w2[e2])
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.embed_dim),
                               ref, rtol=2e-4, atol=2e-5)


def test_pipelined_lm_matches_sequential_logits():
    """PipelinedLM with re-stacked identical parameters produces the
    SAME logits as the stock TransformerLM (4 stages x 1 layer)."""
    from horovod_tpu.parallel import PipelinedLM

    cfg = TransformerConfig(vocab_size=64, num_layers=4, num_heads=4,
                            head_dim=8, max_seq_len=16,
                            dtype=jnp.float32)
    mesh = spmd.create_mesh({"stage": 4}, devices=jax.devices()[:4])
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (4, 16)), jnp.int32)

    lm = TransformerLM(cfg)
    variables = jax.jit(lm.init)(jax.random.key(0), tokens)
    ref_logits = jax.jit(lm.apply)(variables, tokens)

    plm = PipelinedLM(cfg, mesh, num_microbatches=2)
    params = plm.from_transformer_params(variables)
    logits = jax.jit(plm.apply)(params, tokens)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(ref_logits), atol=2e-4)


def test_pipelined_lm_trains_with_dp():
    """dp x pp on the full flagship model: loss decreases under SGD
    through the pipelined tower."""
    from horovod_tpu.parallel import PipelinedLM
    from horovod_tpu.models.transformer import lm_loss

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            head_dim=8, max_seq_len=16,
                            dtype=jnp.float32)
    mesh = spmd.create_mesh({"data": 2, "stage": 2},
                            devices=jax.devices()[:4])
    tokens = jnp.asarray(
        np.tile(np.arange(16, dtype=np.int32)[None], (8, 1)))

    plm = PipelinedLM(cfg, mesh, num_microbatches=2, data_axis="data")
    params = jax.jit(plm.init)(jax.random.key(0), tokens)

    @jax.jit
    def step(p):
        """The loss at ``p`` and the parameters a step on: the
        pipelined tower forward and backward, one program."""
        loss, g = jax.value_and_grad(
            lambda p: lm_loss(plm.apply(p, tokens), tokens))(p)
        return loss, jax.tree_util.tree_map(lambda a, g: a - 0.5 * g, p, g)

    losses = []
    for _ in range(7):      # the loss before each of six steps, and after
        loss, params = step(params)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_pipelined_lm_rejects_bad_configs():
    from horovod_tpu.parallel import PipelinedLM
    mesh = spmd.create_mesh({"stage": 4}, devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="divide evenly"):
        PipelinedLM(TransformerConfig(vocab_size=64, num_layers=3,
                                      num_heads=2, head_dim=4,
                                      dtype=jnp.float32),
                    mesh, num_microbatches=2)
    with pytest.raises(ValueError, match="homogeneous"):
        PipelinedLM(TransformerConfig(vocab_size=64, num_layers=4,
                                      num_heads=2, head_dim=4,
                                      dtype=jnp.float32, num_experts=2),
                    mesh, num_microbatches=2)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) sequence parallelism
# ---------------------------------------------------------------------------

def test_ulysses_matches_reference():
    """Sequence sharded over 4 devices via all-to-all must reproduce
    single-device causal attention exactly (each device attends over
    the full sequence — no approximation anywhere)."""
    from horovod_tpu.parallel import make_ulysses_attention
    mesh = spmd.create_mesh({"data": 1, "seq": 4},
                            devices=jax.devices()[:4])
    b, s, h, d = 2, 16, 4, 8
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    attn = make_ulysses_attention(mesh, data_axis="data",
                                  seq_axis="seq")
    out = jax.jit(partial(attn, causal=True))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(jax.jit(causal_attention)(q, k, v)),
        atol=2e-5)


def test_ulysses_trainer_matches_dense_loss(dense_loss_over_seq):
    """Training loss with Ulysses attention == dense attention loss
    (mirror of the ring-attention equivalence test)."""
    from horovod_tpu.parallel import make_ulysses_attention
    ulys = _first_loss_over_seq(lambda mesh: make_ulysses_attention(
        mesh, data_axis="data", seq_axis="seq"))
    np.testing.assert_allclose(dense_loss_over_seq, ulys, rtol=1e-4)


def test_ulysses_rejects_indivisible_heads():
    from horovod_tpu.parallel import make_ulysses_attention
    mesh = spmd.create_mesh({"data": 1, "seq": 4},
                            devices=jax.devices()[:4])
    attn = make_ulysses_attention(mesh, data_axis="data",
                                  seq_axis="seq")
    q = jnp.zeros((1, 16, 3, 8), jnp.float32)  # 3 heads over 4 devices
    with pytest.raises(ValueError, match="divisible"):
        attn(q, q, q, True)


def test_seq_parallel_attention_respects_causal_flag():
    """attention_fn(q, k, v, causal=False) must run UNmasked attention
    (regression: the flag used to be silently dropped)."""
    from horovod_tpu.parallel import (
        make_ring_attention, make_ulysses_attention,
    )
    mesh = spmd.create_mesh({"data": 1, "seq": 4},
                            devices=jax.devices()[:4])
    b, s, h, d = 1, 16, 4, 8
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    ref = jax.jit(partial(causal_attention, causal=False))(q, k, v)
    uly = make_ulysses_attention(mesh, data_axis="data", seq_axis="seq")
    np.testing.assert_allclose(
        np.asarray(jax.jit(partial(uly, causal=False))(q, k, v)),
        np.asarray(ref), atol=2e-5)
    ring = make_ring_attention(mesh, data_axis="data", seq_axis="seq",
                               model_axis=None)
    np.testing.assert_allclose(
        np.asarray(jax.jit(partial(ring, causal=False))(q, k, v)),
        np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# FSDP (ZeRO-3-style) parameter sharding
# ---------------------------------------------------------------------------

def test_fsdp_sharding_picks_largest_free_divisible_dim():
    from horovod_tpu.parallel import fsdp_sharding
    mesh = spmd.create_mesh({"data": 4, "model": 2})
    params = {
        "big": np.zeros((12, 64), np.float32),      # dim1 largest, both div by 4
        "tall": np.zeros((64, 6), np.float32),      # only dim0 divisible
        "bias": np.zeros((64,), np.float32),        # < min_size: untouched
        "odd": np.zeros((33, 35), np.float32),      # nothing divisible by 4
    }
    sh = fsdp_sharding(params, mesh, axis="data", min_size=128)
    assert sh["big"].spec == P(None, "data")
    assert sh["tall"].spec == P("data", None)
    assert sh["bias"].spec == P()
    assert sh["odd"].spec == P()


def test_fsdp_sharding_composes_with_tp_base():
    from jax.sharding import NamedSharding
    from horovod_tpu.parallel import fsdp_sharding
    mesh = spmd.create_mesh({"data": 4, "model": 2})
    params = {"k": np.zeros((16, 64), np.float32)}
    base = {"k": NamedSharding(mesh, P(None, "model"))}
    sh = fsdp_sharding(params, mesh, axis="data", base=base,
                       min_size=128)
    # dim1 is claimed by tp; fsdp must take the remaining dim0
    assert sh["k"].spec == P("data", "model")


def test_trainer_fsdp_shards_params_and_opt_state():
    import optax
    mesh = spmd.create_mesh({"data": 8})
    model = TransformerLM(_tiny_cfg())
    trainer = Trainer(model, mesh, optax.adam(1e-2),
                      TrainerConfig(model_axis=None, fsdp_axis="data"))
    tokens = np.tile(np.arange(16, dtype=np.int32)[None], (8, 1))
    state = trainer.init(jax.random.key(0), {"tokens": tokens})

    def specs(tree):
        return {jax.tree_util.keystr(k): getattr(v.sharding, "spec", P())
                for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}

    psp = specs(state["params"])
    sharded = [k for k, s in psp.items() if "data" in str(s)]
    assert sharded, psp  # the big matrices picked up the fsdp axis
    assert any("embedding" in k for k in sharded), sharded
    # optimizer moments inherit the parameter shardings via jit
    osp = specs(state["opt_state"])
    assert any("data" in str(s) for s in osp.values()), osp

    state, l0 = trainer.train_step(state, {"tokens": tokens})
    state, l1 = trainer.train_step(state, {"tokens": tokens})
    assert np.isfinite(l0) and float(l1) < float(l0)


def test_trainer_fsdp_matches_plain_dp():
    """FSDP is a memory layout, not a math change: training under
    fsdp_axis must track the plain data-parallel run."""
    import optax
    tokens = np.tile(np.arange(16, dtype=np.int32)[None], (8, 1))
    batch = {"tokens": tokens}

    def run(fsdp):
        mesh = spmd.create_mesh({"data": 8})
        trainer = Trainer(
            TransformerLM(_tiny_cfg()), mesh, optax.sgd(1e-2),
            TrainerConfig(model_axis=None,
                          fsdp_axis="data" if fsdp else None))
        state = trainer.init(jax.random.key(0), batch)
        losses = []
        for _ in range(3):
            state, loss = trainer.train_step(state, batch)
            losses.append(float(loss))
        return losses

    np.testing.assert_allclose(run(False), run(True), rtol=2e-4)


# ---------------------------------------------------------------------------
# Chunked vocab loss
# ---------------------------------------------------------------------------

def test_chunked_lm_loss_matches_default():
    """make_chunked_lm_loss must equal the default full-logits loss in
    value AND gradient (fp32 tolerance), including a chunk size that
    does not divide seq-1 (padding path) and MoE aux handling."""
    import optax
    from horovod_tpu.parallel import make_chunked_lm_loss
    from horovod_tpu.parallel.trainer import _default_lm_loss

    cfg = TransformerConfig(vocab_size=97, num_layers=2, num_heads=2,
                            head_dim=8, max_seq_len=24,
                            dtype=jnp.float32, num_experts=2,
                            moe_every=2)
    model = TransformerLM(cfg)
    tokens = np.random.RandomState(0).randint(
        0, 97, (3, 24)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.key(0), tokens)

    # seq-1 = 23, chunk 8 -> pad 1
    chunked = make_chunked_lm_loss(chunk=8)

    def l_default(p):
        return _default_lm_loss(model.apply, p, {"tokens": tokens})

    def l_chunked(p):
        return chunked(model.apply, p, {"tokens": tokens})

    v0, g0 = jax.jit(jax.value_and_grad(l_default))(params)
    v1, g1 = jax.jit(jax.value_and_grad(l_chunked))(params)
    np.testing.assert_allclose(float(v0), float(v1), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), g0, g1)


def test_chunked_lm_loss_trains_in_trainer():
    import optax
    from horovod_tpu.parallel import make_chunked_lm_loss
    mesh = spmd.create_mesh({"data": 8})
    trainer = Trainer(TransformerLM(_tiny_cfg()), mesh, optax.adam(1e-2),
                      TrainerConfig(model_axis=None),
                      loss_fn=make_chunked_lm_loss(chunk=8))
    tokens = np.tile(np.arange(16, dtype=np.int32)[None], (8, 1))
    batch = {"tokens": tokens}
    state = trainer.init(jax.random.key(0), batch)
    losses = []
    for _ in range(5):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
