"""Model zoo shape/grad sanity (fp32 on CPU devices)."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import (
    MnistConvNet, ResNet18, TransformerConfig, TransformerLM,
)
from horovod_tpu.models.transformer import causal_attention, lm_loss

pytestmark = pytest.mark.interpreter_of_its_own


def test_mnist_convnet_forward():
    model = MnistConvNet()
    x = jnp.zeros((2, 28, 28, 1))
    params = jax.jit(model.init)(jax.random.key(0), x)
    out = jax.jit(model.apply)(params, x)
    assert out.shape == (2, 10)


def test_resnet18_forward_train_eval():
    model = ResNet18(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    # each under one jit: op by op the two are hundreds of compilations
    variables = jax.jit(partial(model.init, train=False))(
        jax.random.key(0), x)
    out = jax.jit(partial(model.apply, train=False))(variables, x)
    assert out.shape == (2, 10)
    out, updates = jax.jit(partial(
        model.apply, train=True, mutable=["batch_stats"]))(variables, x)
    assert out.shape == (2, 10)
    assert "batch_stats" in updates


def test_transformer_forward_and_loss_grad():
    cfg = TransformerConfig(vocab_size=128, num_layers=2, num_heads=2,
                            head_dim=8, max_seq_len=16, dtype=jnp.float32)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), tokens)
    logits = jax.jit(model.apply)(params, tokens)
    assert logits.shape == (2, 16, 128)
    assert logits.dtype == jnp.float32

    def loss(p):
        return lm_loss(model.apply(p, tokens), tokens)

    g = jax.jit(jax.grad(loss))(params)
    flat = jax.tree_util.tree_leaves(g)
    assert all(np.isfinite(np.asarray(t)).all() for t in flat)


def test_causal_attention_masks_future():
    b, s, h, d = 1, 6, 2, 4
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    out1 = causal_attention(q, k, v)
    # Perturbing future keys/values must not change earlier outputs.
    k2 = k.at[:, -1].set(100.0)
    v2 = v.at[:, -1].set(100.0)
    out2 = causal_attention(q, k2, v2)
    np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                               np.asarray(out2[:, :-1]), atol=1e-5)
    assert not np.allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]))


def test_vit_forward_and_trains():
    """ViT: patchify shape math, finite loss, and a few improving
    data-parallel steps on the 8-device mesh with fsdp sharding (the
    generic largest-free-dim rule must handle ViT params unmodified)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from horovod_tpu import spmd
    from horovod_tpu.models import ViT, ViTConfig
    from horovod_tpu.parallel import fsdp_sharding

    cfg = ViTConfig(image_size=32, patch_size=8, num_classes=10,
                    embed_dim=64, num_layers=2, num_heads=4,
                    dtype=jnp.float32)
    model = ViT(cfg)
    mesh = spmd.create_mesh({"data": 8})
    rng = np.random.RandomState(0)
    x = rng.randn(16, 32, 32, 3).astype(np.float32)
    y = rng.randint(0, 10, 16)

    params = jax.jit(model.init)(jax.random.key(0), jnp.asarray(x[:1]))
    logits = jax.jit(model.apply)(params, jnp.asarray(x[:2]))
    assert logits.shape == (2, 10) and np.isfinite(np.asarray(logits)).all()

    # fsdp shardings apply generically (big matrices pick up the axis)
    sh = fsdp_sharding(params, mesh, axis="data")
    specs = [s.spec for s in jax.tree_util.tree_leaves(
        sh, is_leaf=lambda s: hasattr(s, "spec"))]
    assert any("data" in str(s) for s in specs)
    params = jax.tree_util.tree_map(jax.device_put, params, sh)

    tx = optax.adam(1e-3)
    opt_state = jax.jit(tx.init)(params)

    @jax.jit
    def step(p, s, xb, yb):
        def loss_fn(p):
            lg = model.apply(p, xb)
            oh = jax.nn.one_hot(yb, 10)
            return -jnp.mean(jnp.sum(jax.nn.log_softmax(lg) * oh, -1))
        loss, g = jax.value_and_grad(loss_fn)(p)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    xb = jax.device_put(jnp.asarray(x), spmd.batch_sharding(mesh))
    yb = jax.device_put(jnp.asarray(y), spmd.batch_sharding(mesh))
    losses = []
    for _ in range(6):
        params, opt_state, loss = step(params, opt_state, xb, yb)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
