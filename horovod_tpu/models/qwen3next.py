"""Hybrid linear-attention sparse decoder as Qwen3-Next
(``qwen3_next``) lays it out: Gated DeltaNet in three layers of four,
gated softmax attention in the fourth, an expert layer in every one.

Pre-norm residual blocks, no biases anywhere; every RMSNorm of the
residual path is **zero-centred** (``RMS(x) (1 + w)``, ``w`` starting
at 0)::

    h   = x + mixer(norm1(x))
    out = h + experts(norm2(h))

The mixer of **published** layer ``i`` (``layer_kind``): gated
attention where ``(i + 1) mod full_attention_interval = 0``, Gated
DeltaNet otherwise.

* **Gated DeltaNet** (:class:`GatedDeltaNet`; arXiv:2412.06464): ``[q,
  k, v, z] = x W_qkvz``, ``[b, a] = x W_ba``; a causal depthwise
  convolution and SiLU on ``[q, k, v]``, q and k L2-normalised by head,
  q scaled by ``Dk^-1/2`` (:class:`QkvPrologue`: one kernel pass each
  way, float32 inside, ``parallel.qkv_prologue``); ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` in float32;
  the gated delta rule
  (``parallel.gated_delta``: a float32 matrix state a value head, each
  key head serving ``Hv / Hk`` value heads); ``y = RMS(o) w_n
  silu(z)`` by head (:class:`GatedHeadNorm`: one kernel pass each way,
  float32 inside, ``parallel.delta_epilogue``); ``out = y W_o``.
* **Gated attention** (:class:`GatedAttention`; arXiv:2505.06708):
  ``[q, gate] = x W_q`` split by head; a zero-centred RMSNorm over the
  head on q and k; rotary on the first ``partial_rotary_factor`` of the
  head; causal softmax attention over fewer key-value heads than query
  heads (the flash kernels: nothing is repeated in HBM); ``out = (a
  sigmoid(gate)) W_o``.
* **Expert layer**: ``glm_moe.ExpertLayer``, the one both sparse models
  share, told here that the scores are a softmax over all the experts
  with no bias, that the normalised weights are not scaled, and that
  the shared expert has a sigmoid gate of its own.

The model may hold any subset of the published layers
(``kept_layers``); each keeps its published index, which fixes its
kind. Every block is recomputed in the backward pass with its kernels'
outputs kept, the prologue's q, k and v and the gated norm's y made
again. The model returns the pre-head states and the expert layers'
load counts; ``train_steps.qwen3next_loss_fn`` turns them into the
next-token cross-entropy on an untied head.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.glm_moe import ExpertLayer, _keep_kernel_outputs
from horovod_tpu.models.transformer import apply_rope
from horovod_tpu.parallel.delta_epilogue import delta_epilogue
from horovod_tpu.parallel.gated_delta import (
    LANES, gated_delta_rule, laid_columns, lay_heads, take_heads,
)
from horovod_tpu.parallel.qkv_prologue import qkv_prologue


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    published_layers: int = 48       # fixes every layer's kind
    kept_layers: Optional[Tuple[int, ...]] = None    # None: all of them
    full_attention_interval: int = 4
    # gated attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # Gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # beta = 2 sigmoid(b): the state's transition I - beta k k^T may
    # have a negative eigenvalue (Olmo-Hybrid's layers do)
    linear_allow_neg_eigval: bool = False
    # Added to the learnt A_log and dt_bias. 0 for trained weights
    # (their leaves hold it); a job that starts from leaves drawn around
    # zero gives the family's starting point here, the log of a decay
    # rate in (0, 16) and softplus^-1 of a step in [0.001, 0.1]: the
    # same function family and the same gradients as leaves initialised
    # there.
    a_log_init: float = 0.0
    dt_bias_init: float = 0.0
    # the expert layer (what ``glm_moe.ExpertLayer`` reads)
    moe_intermediate_size: int = 512
    shared_intermediate_size: int = 512
    n_routed_experts: int = 512      # the router's width
    num_experts_per_tok: int = 10
    scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    shared_expert_gate: bool = True
    topk_weight_eps: float = 0.0
    n_group: int = 1
    topk_group: int = 1
    expert_activation: str = "silu"
    row_tier_headroom: float = 2.0
    # The share of the experts this chip holds: ids
    # [expert_offset, expert_offset + experts_held).
    experts_held: int = 512
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def layers(self) -> Tuple[int, ...]:
        return tuple(range(self.published_layers)) \
            if self.kept_layers is None else tuple(self.kept_layers)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)


def layer_kind(index: int, full_attention_interval: int) -> str:
    """``attention`` or ``delta``: the mixer of published layer
    ``index``."""
    return "attention" if (index + 1) % full_attention_interval == 0 \
        else "delta"


def best_grouped_attention(q, k, v, window=None):
    """The flash kernels on the TPU, the same mathematics dense
    elsewhere. q: [B,S,H,D]; k, v: [B,S,Hkv,D]; ``window`` None is the
    causal mask alone."""
    from horovod_tpu.parallel import flash_attention as fa
    if jax.default_backend() == "tpu":
        return fa.flash_attention(q, k, v, causal=True, window=window)
    return fa._dense_reference(q, k, v, True, 0, 0, window)


def head_lanes() -> int:
    """The lanes a run of a delta rule's columns is laid out to between
    its kernels: the vector register's where they are compiled, 1 (as
    the heads come) under the interpreter, which takes any block."""
    return LANES if jax.default_backend() == "tpu" else 1


def _dense(cfg: Qwen3NextConfig, features, name: str, axis=-1):
    return nn.DenseGeneral(features, axis=axis, use_bias=False,
                           dtype=cfg.dtype, name=name)


class ZeroCentredRMSNorm(nn.Module):
    """``RMS(x) (1 + w)`` over the last axis, statistics in float32."""

    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        weight = self.param("weight", nn.initializers.zeros,
                            (x.shape[-1],), jnp.float32)
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), -1, keepdims=True) + self.eps)
        return (xf * (1.0 + weight)).astype(self.dtype)


def _norm(cfg: Qwen3NextConfig, name: str):
    return ZeroCentredRMSNorm(cfg.rms_norm_eps, cfg.dtype, name=name)


class QkvPrologue(nn.Module):
    """``(q, k, v)``, each [B, S, heads x head_dim] in ``x``'s type,
    from the leading ``channels`` columns of the fused projection's
    output: a causal depthwise convolution of ``taps`` taps (``kernel``
    [taps, channels] float32, the leaf ``CausalDepthwiseConv`` holds)
    and SiLU; the first ``normalised_heads`` heads (q's and k's)
    L2-normalised, the first ``scaled_heads`` of them (q's) scaled by
    ``head_dim ** -0.5``. One pass of ``parallel.qkv_prologue`` each
    way; the Gated DeltaNet's and Kimi delta attention's both. With
    ``lay`` (a run of columns, the lanes it is laid out to) ``x`` comes
    laid out (``lay_heads``), the kernel is laid out here, and q, k and
    v leave so."""

    taps: int
    channels: int
    head_dim: int
    normalised_heads: int
    scaled_heads: int
    lay: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (self.taps, self.channels), jnp.float32)
        return qkv_prologue(x, lay_heads(kernel, *self.lay),
                            laid_columns(self.head_dim, *self.lay),
                            self.normalised_heads, self.scaled_heads,
                            key_dim=self.head_dim)


class GatedHeadNorm(nn.Module):
    """``y`` [B, S, heads x head_dim] in ``o``'s type for the output
    projection: the rule's output ``o`` [B, S, heads x head_dim]
    through an RMSNorm a head (``scale`` [head_dim] float32, the leaf
    ``nn.RMSNorm`` holds) times ``activation`` of the gate: a head's
    scalar ``gate`` [B, S, heads], or an element's in the ``heads x
    head_dim`` columns of ``gate`` from ``gate_start`` on. One pass of
    ``parallel.delta_epilogue`` each way; the Gated DeltaNet's and Kimi
    delta attention's both. With ``lay`` (a run of columns, the lanes
    it is laid out to) ``o`` and an element's gate come laid out
    (``lay_heads``), the scale is laid out here, and y leaves so."""

    head_dim: int
    activation: str
    eps: float
    lay: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, o, gate, gate_start: int = 0):
        scale = self.param("scale", nn.initializers.ones, (self.head_dim,),
                           jnp.float32)
        return delta_epilogue(o, lay_heads(scale, *self.lay), gate,
                              laid_columns(self.head_dim, *self.lay),
                              self.activation, gate_start, self.eps,
                              filled=self.head_dim)


class GatedDeltaNet(nn.Module):
    """``cfg`` says what differs between the models that have the layer
    (``Qwen3NextConfig``, ``olmo_hybrid.OlmoHybridConfig``): the heads
    and their widths, and whether ``beta`` reaches 2
    (``linear_allow_neg_eigval``). **Heads off the lane tile** (key
    heads of 96 over value heads of 192) travel laid out from the
    projection to the output projection: every ``gcd(Dk, Dv)`` columns
    of ``qkvz`` behind zeros up to whole lanes (``head_lanes``;
    ``parallel.gated_delta.lay_heads``: one copy in, one out, each way),
    so that the three kernels between walk whole tiles; at heads of 128
    nothing is laid out and nothing copied."""

    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        keys, values = hk * dk, hv * dv
        lay = (math.gcd(dk, dv), head_lanes())
        wide = lambda n: laid_columns(n, *lay)
        beta_max = 2.0 if cfg.linear_allow_neg_eigval else 1.0
        lead = x.shape[:2]
        with jax.named_scope("gdn.proj"):
            qkvz = _dense(cfg, 2 * keys + 2 * values, "in_proj_qkvz")(x)
            ba = _dense(cfg, 2 * hv, "in_proj_ba")(x).astype(jnp.float32)
        with jax.named_scope("gdn.conv"):
            qkvz = lay_heads(qkvz, *lay)
            q, k, v = QkvPrologue(
                cfg.linear_conv_kernel_dim, 2 * keys + values, dk, 2 * hk, hk,
                lay, name="conv")(qkvz)
        a_log = self.param("A_log", nn.initializers.zeros, (hv,),
                           jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (hv,),
                             jnp.float32)
        with jax.named_scope("gdn.rule"):
            # float32 up to the kernel's door: g is an exponent's argument
            beta = jax.nn.sigmoid(ba[..., :hv])
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(a_log + cfg.a_log_init) * jax.nn.softplus(
                ba[..., hv:] + dt_bias + cfg.dt_bias_init)
            o = gated_delta_rule(
                q.reshape(*lead, hk, wide(dk)), k.reshape(*lead, hk, wide(dk)),
                v.reshape(*lead, hv, wide(dv)), g, beta, beta_max=beta_max,
                filled=(dk, dv))
        with jax.named_scope("gdn.gate"):
            # z is qkvz's last columns, read where they lie
            y = GatedHeadNorm(dv, "silu", cfg.rms_norm_eps, lay,
                              name="norm")(
                o.reshape(*lead, wide(values)), qkvz,
                wide(2 * keys + values))
            y = take_heads(y, *lay)
        with jax.named_scope("gdn.proj"):
            return _dense(cfg, cfg.hidden_size, "out_proj")(y)


class GatedAttention(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        rot = cfg.rotary_dim
        q_gate = _dense(cfg, (h, 2 * d), "q")(x)
        q, gate = q_gate[..., :d], q_gate[..., d:]
        k = _dense(cfg, (kv, d), "k")(x)
        v = _dense(cfg, (kv, d), "v")(x)
        q, k = _norm(cfg, "q_norm")(q), _norm(cfg, "k_norm")(k)
        rotary = lambda t: jnp.concatenate(
            [apply_rope(t[..., :rot], positions, cfg.rope_theta),
             t[..., rot:]], -1)
        out = best_grouped_attention(rotary(q), rotary(k), v)
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)) \
            .astype(out.dtype)
        return _dense(cfg, cfg.hidden_size, "o", axis=(-2, -1))(out)


class Block(nn.Module):
    """``(x, counts)``: published layer ``index``."""

    cfg: Qwen3NextConfig
    index: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h = _norm(cfg, "norm1")(x)
        if layer_kind(self.index, cfg.full_attention_interval) == "delta":
            x = x + GatedDeltaNet(cfg, name="mixer")(h)
        else:
            with jax.named_scope("gated_attn"):
                x = x + GatedAttention(cfg, name="mixer")(h, positions)
        y, counts = ExpertLayer(cfg, name="moe")(_norm(cfg, "norm2")(x))
        return x + y, counts


# Every block is recomputed in the backward pass: four blocks'
# activations at 16,384 tokens do not fit a chip beside 7.5 GB of state.
RematBlock = nn.remat(Block, policy=_keep_kernel_outputs)


class Qwen3NextLM(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, tokens):
        """tokens [B, S] -> ``(hidden, counts)``: the pre-head states
        [B, S, D] after the final norm (training goes through
        ``lm_loss_from_hidden``, which never builds the logits) and the
        expert layers' counts [layers, experts_held + 2]."""
        cfg = self.cfg
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None], tokens.shape)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed")(tokens)
        counts = []
        for i in cfg.layers:
            x, c = RematBlock(cfg, i, name=f"layer_{i}")(x, positions)
            counts.append(c)
        hidden = _norm(cfg, "norm_f")(x)
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")
        if self.is_initializing():      # creates the head's parameters
            head(hidden.astype(jnp.float32))
        return hidden, jnp.stack(counts)
