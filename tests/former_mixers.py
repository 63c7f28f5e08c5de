"""The two delta-rule mixers as they were before each kernel PR took a
part of them into Pallas, leaf for leaf, and the comparison of a mixer
with such a former self: what ``tests/test_qkv_prologue.py`` (before
PR 42's prologue) and ``tests/test_delta_epilogue.py`` (before PR 43's
epilogue) both hold the present mixers to, at the same shapes and from
the same leaves. The present mixer's leaves, output and gradients are
made once for both files."""

import contextlib
import functools
import io

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .compiled import out_and_vjp

from horovod_tpu.models import glm_moe, ling3flash, qwen3next
from horovod_tpu.models.phi4flash import CausalDepthwiseConv
from horovod_tpu.parallel.gated_delta import gated_delta_rule
from horovod_tpu.parallel.kda import kimi_delta_attention


def l2_normalised(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def _gated_norm(cfg, o, gate):
    """``nn.RMSNorm`` a head in float32 times the gate, as both mixers
    ran it before the epilogue."""
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=jnp.float32,
                      param_dtype=jnp.float32, name="norm")(
                          o.astype(jnp.float32)) * gate


class _KimiDeltaAttention(nn.Module):
    """``ling3flash.KimiDeltaAttention`` around what a subclass makes
    q, k and v with: the projections, the gates, the rule, the norm a
    head in float32 under the sigmoid gate."""

    cfg: ling3flash.Ling3FlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, d = cfg.num_heads, cfg.kda_head_dim
        width = h * d
        lead = x.shape[:2]
        q, k, v = self.qkv(glm_moe._dense(cfg, 3 * width, "in_proj_qkv")(x))
        a_log = self.param("A_log", nn.initializers.zeros, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (width,),
                             jnp.float32)
        f = nn.Dense(width, use_bias=False, dtype=jnp.float32,
                     name="in_proj_f")(x)
        bz = nn.Dense(2 * h, use_bias=False, dtype=jnp.float32,
                      name="in_proj_bz")(x)
        f = (f + dt_bias + cfg.dt_bias_init).reshape(*lead, h, d)
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log + cfg.a_log_init)[:, None] * f)
        beta = jax.nn.sigmoid(bz[..., :h])
        o = kimi_delta_attention(
            q.reshape(*lead, h, d), k.reshape(*lead, h, d),
            v.reshape(*lead, h, d), g, beta)
        y = _gated_norm(cfg, o, jax.nn.sigmoid(bz[..., h:])[..., None])
        return glm_moe._dense(cfg, cfg.hidden_size, "out_proj")(
            y.astype(cfg.dtype).reshape(*lead, width))


class KimiDeltaAttentionBeforeThePrologue(_KimiDeltaAttention):
    """As it was at PR 41: the convolution in float32, SiLU, the L2
    norm a head, the scale and the casts, op by op."""

    def qkv(self, qkv):
        cfg = self.cfg
        h, d = cfg.num_heads, cfg.kda_head_dim
        width, lead = h * d, qkv.shape[:2]
        qkv = nn.silu(CausalDepthwiseConv(
            cfg.short_conv_kernel_size, use_bias=False, name="conv")(qkv))
        q = l2_normalised(qkv[..., :width].reshape(*lead, h, d)) * d ** -0.5
        k = l2_normalised(qkv[..., width:2 * width].reshape(*lead, h, d))
        return (q.astype(cfg.dtype), k.astype(cfg.dtype),
                qkv[..., 2 * width:].astype(cfg.dtype))


class KimiDeltaAttentionBeforeTheEpilogue(_KimiDeltaAttention):
    """As it was at PR 42: the prologue's kernels, the gated norm op by
    op."""

    def qkv(self, qkv):
        cfg = self.cfg
        h, d = cfg.num_heads, cfg.kda_head_dim
        return qwen3next.QkvPrologue(
            cfg.short_conv_kernel_size, 3 * h * d, d, 2 * h, h,
            name="conv")(qkv)


class _GatedDeltaNet(nn.Module):
    """``qwen3next.GatedDeltaNet`` around what a subclass makes q, k
    and v with: the projections, the gates, the rule, the norm a head
    in float32 under ``silu(z)``."""

    cfg: qwen3next.Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        keys, values = hk * dk, hv * dv
        lead = x.shape[:2]
        qkvz = qwen3next._dense(cfg, 2 * keys + 2 * values, "in_proj_qkvz")(x)
        ba = qwen3next._dense(cfg, 2 * hv, "in_proj_ba")(x) \
            .astype(jnp.float32)
        q, k, v = self.qkv(qkvz)
        a_log = self.param("A_log", nn.initializers.zeros, (hv,),
                           jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (hv,),
                             jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log + cfg.a_log_init) * jax.nn.softplus(
            ba[..., hv:] + dt_bias + cfg.dt_bias_init)
        o = gated_delta_rule(
            q.reshape(*lead, hk, dk), k.reshape(*lead, hk, dk),
            v.reshape(*lead, hv, dv), g, beta)
        z = qkvz[..., 2 * keys + values:].reshape(*lead, hv, dv)
        y = _gated_norm(cfg, o, nn.silu(z.astype(jnp.float32)))
        return qwen3next._dense(cfg, cfg.hidden_size, "out_proj")(
            y.astype(cfg.dtype).reshape(*lead, values))


class GatedDeltaNetBeforeThePrologue(_GatedDeltaNet):
    """As it was at PR 33."""

    def qkv(self, qkvz):
        cfg = self.cfg
        hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        keys = hk * dk
        values = cfg.linear_num_value_heads * cfg.linear_value_head_dim
        lead = qkvz.shape[:2]
        qkv = nn.silu(CausalDepthwiseConv(
            cfg.linear_conv_kernel_dim, use_bias=False, name="conv")(
                qkvz[..., :2 * keys + values]))
        q = l2_normalised(qkv[..., :keys].reshape(*lead, hk, dk)) * dk ** -0.5
        k = l2_normalised(qkv[..., keys:2 * keys].reshape(*lead, hk, dk))
        return (q.astype(cfg.dtype), k.astype(cfg.dtype),
                qkv[..., 2 * keys:].astype(cfg.dtype))


class GatedDeltaNetBeforeTheEpilogue(_GatedDeltaNet):
    """As it was at PR 42."""

    def qkv(self, qkvz):
        cfg = self.cfg
        hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        values = cfg.linear_num_value_heads * cfg.linear_value_head_dim
        return qwen3next.QkvPrologue(
            cfg.linear_conv_kernel_dim, 2 * hk * dk + values, dk, 2 * hk,
            hk, name="conv")(qkvz)


SEQ, HIDDEN = 40, 32
# name -> (the mixer, its configuration, its former selves by the
# kernel that came after them)
MIXERS = {
    "kimi_delta_attention": (
        ling3flash.KimiDeltaAttention,
        ling3flash.Ling3FlashConfig(
            hidden_size=HIDDEN, num_heads=2, kda_head_dim=16,
            dt_bias_init=-2.0),
        {"prologue": KimiDeltaAttentionBeforeThePrologue,
         "epilogue": KimiDeltaAttentionBeforeTheEpilogue}),
    "gated_deltanet": (
        qwen3next.GatedDeltaNet,
        qwen3next.Qwen3NextConfig(
            hidden_size=HIDDEN, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, a_log_init=0.5, dt_bias_init=-2.0),
        {"prologue": GatedDeltaNetBeforeThePrologue,
         "epilogue": GatedDeltaNetBeforeTheEpilogue}),
}


def _operands():
    x, cot = (jax.random.normal(jax.random.key(seed), (2, SEQ, HIDDEN))
              .astype(jnp.bfloat16) for seed in (2, 3))
    return x, cot


def _initialised(module):
    """``module.init`` as one program (op by op it is the mixer's whole
    forward in interpreter mode, a program a primitive)."""
    return jax.jit(module.init)(jax.random.key(1), _operands()[0])["params"]


@functools.cache
def _now(name):
    """The present mixer's leaves as initialised, the leaves it is run
    with, its output and every leaf's gradient: once for both files."""
    mixer, cfg, _ = MIXERS[name]
    x, cot = _operands()
    initial = _initialised(mixer(cfg))
    p = jax.tree_util.tree_map(        # leaves that do something
        lambda a: a + 0.3 * jax.random.normal(jax.random.key(a.size),
                                              a.shape), initial)
    return initial, p, out_and_vjp(
        lambda p, x: mixer(cfg).apply({"params": p}, x), cot, p, x)


def a_mixer_is_its_former_self_in_bfloat16(name, before):
    """Output and every leaf's gradient to bfloat16's tolerance, from
    the same leaves: the tree a checkpoint addresses is unchanged, the
    initial values too (a leaf's draw follows its path). Returns the
    leaves as initialised."""
    _, cfg, formers = MIXERS[name]
    former = formers[before](cfg)
    initial, p, (got, got_grads) = _now(name)
    p_former = _initialised(former)
    assert jax.tree_util.tree_structure(initial) \
        == jax.tree_util.tree_structure(p_former)
    for a, b in zip(jax.tree_util.tree_leaves(initial),
                    jax.tree_util.tree_leaves(p_former)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    x, cot = _operands()
    want, want_grads = out_and_vjp(
        lambda p, x: former.apply({"params": p}, x), cot, p, x)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    close = lambda g, w, what: np.testing.assert_allclose(
        f32(g), f32(w), rtol=2 ** -5,
        atol=2 ** -6 * float(np.abs(f32(w)).max()), err_msg=what)
    close(got, want, "out")
    flat = lambda t: {jax.tree_util.keystr(k): v for k, v in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    got_grads, want_grads = flat(got_grads), flat(want_grads)
    for path, w in want_grads.items():
        assert np.abs(f32(w)).max() > 0, path
        close(got_grads[path], w, path)
    return initial


# -- what a recomputed block keeps ------------------------------------------

BLOCKS = {"kimi_delta_attention": ling3flash.RematBlock,
          "gated_deltanet": qwen3next.RematBlock}


@functools.cache
def a_recomputed_blocks_backward(name):
    """``(calls, kept)`` of the gradient of a recomputed block of
    ``name``'s model: ``calls(kernel)`` counts a kernel's calls in its
    jaxpr, ``kept`` are the lines of ``print_saved_residuals`` for what
    is no argument. Both are read off the trace, so the block's leaves
    are shapes alone."""
    from jax.ad_checkpoint import print_saved_residuals
    block = BLOCKS[name](MIXERS[name][1], 0)
    x = jax.ShapeDtypeStruct((1, SEQ, HIDDEN), jnp.bfloat16)
    positions = jnp.zeros((1, SEQ), jnp.int32)
    p = jax.eval_shape(block.init, jax.random.key(0), x, positions)

    def loss(p, x):
        return jnp.sum(block.apply(p, x, positions)[0].astype(jnp.float32))

    text = str(jax.make_jaxpr(jax.grad(loss))(p, x))
    calls = lambda kernel: text.count(f"name={kernel}\n") \
        + text.count(f"name={kernel} ")
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        print_saved_residuals(loss, p, x)
    return calls, [line for line in said.getvalue().splitlines()
                   if "from the argument" not in line]
