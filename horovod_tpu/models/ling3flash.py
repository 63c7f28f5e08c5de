"""Hybrid linear-attention sparse decoder as Ling-3.0-flash
(``bailing_hybrid``; the language model of Ling-3.0-flash-VL) lays it
out: Kimi delta attention in five layers of six, latent attention in
the sixth, a dense SwiGLU in the leading layers and a group-limited
expert layer with a shared expert in every later one.

Pre-norm residual blocks, plain RMSNorm, no biases anywhere::

    h   = x + mixer(norm1(x))
    out = h + ff(norm2(h))

The mixer of **published** layer ``i`` (``layer_kind``): latent
attention where ``(i + 1) mod layer_group_size = 0``, Kimi delta
attention otherwise.

* **Kimi delta attention** (:class:`KimiDeltaAttention`;
  arXiv:2510.26692): ``[q, k, v] = x W_qkv`` through a causal depthwise
  convolution of ``short_conv_kernel_size`` taps and SiLU, q and k
  L2-normalised by head, q scaled by ``D^-1/2`` (``qwen3next
  .QkvPrologue``: one kernel pass each way, float32 inside,
  ``parallel.qkv_prologue``); ``f = x W_f +
  dt_bias`` a key channel, ``g = kda_lower_bound sigmoid(exp(A_log_h)
  f)`` in float32 (the bounded gate: ``g`` in [lower, 0], which is what
  lets the rule's kernels hold a sub-block of 16 positions in float32);
  ``[b, z] = x W_bz`` a head, ``beta = sigmoid(b)``; the rule with **a
  decay for every key channel** (``parallel.kda``: a float32 matrix
  state a head); ``y_h = sigmoid(z_h) RMS(o_h) w_n``, the norm a head's
  own (``qwen3next.GatedHeadNorm``: one kernel pass each way, float32
  inside, ``parallel.delta_epilogue``); ``out = y W_o``. No rotary.
* **Latent attention**: ``glm_moe.LatentAttention``, told here that q
  has no latent of its own, that q and the assembled k are normed by
  head, and that each head's output has a sigmoid gate.
* ``ff`` of layer ``i``: ``glm_moe.SwiGLU`` of ``intermediate_size``
  where ``i < first_k_dense``; else ``glm_moe.ExpertLayer``, the one
  expert layer of every sparse model here, told that the scores are
  sigmoids beside a correction bias, that the choice is limited to the
  best ``topk_group`` of ``n_group`` groups, that the normalised
  weights are scaled, and that the shared expert has no gate.

The model may hold any subset of the published layers
(``kept_layers``); each keeps its published index, which fixes its
mixer and its feed-forward. Every block is recomputed in the backward
pass with its kernels' outputs kept, the prologue's q, k and v and the
gated norm's y made again. The model returns the pre-head states and
the layers' load counts; ``train_steps.ling3flash_loss_fn`` turns them
into the next-token cross-entropy on an untied head.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.glm_moe import (
    ExpertLayer, LatentAttention, SwiGLU, _dense, _keep_kernel_outputs, _norm,
)
from horovod_tpu.models.qwen3next import GatedHeadNorm, QkvPrologue
from horovod_tpu.parallel.kda import kimi_delta_attention


@dataclasses.dataclass(frozen=True)
class Ling3FlashConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    published_layers: int = 42       # fixes every layer's kind
    kept_layers: Optional[Tuple[int, ...]] = None    # None: all of them
    layer_group_size: int = 6
    first_k_dense: int = 2
    intermediate_size: int = 6144
    num_heads: int = 32              # of both mixers
    # Kimi delta attention
    kda_head_dim: int = 128          # of the key and of the value
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    # Added to the learnt A_log and dt_bias. 0 for trained weights
    # (their leaves hold it); a job that starts from leaves drawn around
    # zero gives the starting point here: the same function family and
    # the same gradients as leaves initialised there.
    a_log_init: float = 0.0
    dt_bias_init: float = 0.0
    # latent attention (what ``glm_moe.LatentAttention`` reads)
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_head_norm: bool = True
    head_output_gate: bool = True
    rope_theta: float = 6e6
    # the expert layer (what ``glm_moe.ExpertLayer`` reads)
    moe_intermediate_size: int = 768
    shared_intermediate_size: int = 768
    n_routed_experts: int = 512      # the router's width
    num_experts_per_tok: int = 8
    scoring: str = "sigmoid"
    routed_scaling_factor: float = 2.5
    shared_expert_gate: bool = False
    topk_weight_eps: float = 0.0
    n_group: int = 8
    topk_group: int = 4
    expert_activation: str = "silu"
    # The first tier of the row buffer over the share held. A chip of
    # 64 holds a sixty-fourth of the experts, and a seeded router's
    # spread over so small a share is wide (a layer's held experts drew
    # 0.65 to 1.5 times their expectation on one seed): at the other
    # models' 2 one seed in six spent a quarter of its steps in the
    # tier that walks every assignment (PERF.md, PR 41).
    row_tier_headroom: float = 8.0
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


def layer_kind(index: int, layer_group_size: int) -> str:
    """``attention`` or ``kda``: the mixer of published layer
    ``index``."""
    return "attention" if (index + 1) % layer_group_size == 0 else "kda"


class KimiDeltaAttention(nn.Module):
    cfg: Ling3FlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, d = cfg.num_heads, cfg.kda_head_dim
        width = h * d
        lead = x.shape[:2]
        with jax.named_scope("kda.proj"):
            qkv = _dense(cfg, 3 * width, "in_proj_qkv")(x)
        with jax.named_scope("kda.conv"):
            q, k, v = QkvPrologue(cfg.short_conv_kernel_size, 3 * width, d,
                                  2 * h, h, name="conv")(qkv)
        a_log = self.param("A_log", nn.initializers.zeros, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (width,),
                             jnp.float32)
        with jax.named_scope("kda.gate"):
            # float32 out of the products: g is an exponent's argument
            f = nn.Dense(width, use_bias=False, dtype=jnp.float32,
                         name="in_proj_f")(x)
            bz = nn.Dense(2 * h, use_bias=False, dtype=jnp.float32,
                          name="in_proj_bz")(x)
            f = (f + dt_bias + cfg.dt_bias_init).reshape(*lead, h, d)
            g = cfg.kda_lower_bound * jax.nn.sigmoid(
                jnp.exp(a_log + cfg.a_log_init)[:, None] * f)
            beta = jax.nn.sigmoid(bz[..., :h])
        with jax.named_scope("kda.rule"):
            o = kimi_delta_attention(
                q.reshape(*lead, h, d), k.reshape(*lead, h, d),
                v.reshape(*lead, h, d), g, beta)
        with jax.named_scope("kda.norm"):
            y = GatedHeadNorm(d, "sigmoid", cfg.rms_norm_eps, name="norm")(
                o.reshape(*lead, width), bz[..., h:])
        with jax.named_scope("kda.proj"):
            return _dense(cfg, cfg.hidden_size, "out_proj")(y)


class Block(nn.Module):
    """``(x, counts)``: published layer ``index``."""

    cfg: Ling3FlashConfig
    index: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h = _norm(cfg, "norm1")(x)
        if layer_kind(self.index, cfg.layer_group_size) == "kda":
            x = x + KimiDeltaAttention(cfg, name="mixer")(h)
        else:
            x = x + LatentAttention(cfg, name="mixer")(h, positions)
        h = _norm(cfg, "norm2")(x)
        if self.index < cfg.first_k_dense:
            y = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
            counts = jnp.zeros((cfg.experts_held + 2,), jnp.int32)
        else:
            y, counts = ExpertLayer(cfg, name="moe")(h)
        return x + y, counts


# Every block is recomputed in the backward pass: seven blocks'
# activations at 16,384 tokens do not fit a chip beside 9.9 GB of state.
RematBlock = nn.remat(Block, policy=_keep_kernel_outputs)


class Ling3FlashLM(nn.Module):
    cfg: Ling3FlashConfig

    @nn.compact
    def __call__(self, tokens):
        """tokens [B, S] -> ``(hidden, counts)``: the pre-head states
        [B, S, D] after the final norm (training goes through
        ``lm_loss_from_hidden``, which never builds the logits) and the
        layers' counts [layers, experts_held + 2], a dense layer's as
        zeros."""
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
