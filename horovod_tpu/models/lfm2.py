"""Convolution-attention sparse decoder as LFM2-24B-A2B (``lfm2_moe``)
lays it out: a gated short convolution in three layers of four,
grouped-head attention with normalised q and k in the fourth, a dense
SwiGLU in the leading layers and an expert layer without a shared
expert in every later one.

Pre-norm residual blocks, plain RMSNorm, no biases anywhere::

    h   = x + op(norm_op(x))
    out = h + ff(norm_ffn(h))

``op`` of **published** layer ``i`` is what ``layer_types[i]`` says.

* **Gated short convolution** (:class:`ShortConv`): ``[B, C, u] = x
  W_in`` in that order; ``c_t = sum_j w_j (B u)_{t - 2 + j}`` a channel
  (depthwise, causal, ``conv_L_cache`` taps, no bias); ``out = (C c)
  W_out``. **No activation function**: both gates are linear in ``x``.
* **Attention** (:class:`NormedGroupedAttention`): q over
  ``num_heads``, k and v over ``num_kv_heads`` heads of ``hidden_size /
  num_heads``; an RMSNorm over the head on q and on k (one weight
  vector for all query heads, one for all key heads); the rotary over
  the whole head, halves paired; causal softmax attention, each
  key-value head serving ``num_heads / num_kv_heads`` query heads (the
  flash kernels: nothing is repeated in HBM); ``out = a W_o``.
* ``ff`` of layer ``i``: ``glm_moe.SwiGLU`` of ``intermediate_size``
  where ``i < num_dense_layers``; else ``glm_moe.ExpertLayer``, the one
  expert layer of every sparse model here, told that the scores are
  sigmoids beside a correction bias, that the chosen weights are
  divided by their sum plus ``topk_weight_eps``, and that there is no
  shared expert (``shared_intermediate_size`` 0).

The model may hold any subset of the published layers
(``kept_layers``); each keeps its published index, which fixes its
operator and its feed-forward. Every block is recomputed in the
backward pass with its kernels' outputs kept. The model returns the
pre-head states (after the final norm, which the published code calls
``embedding_norm``) and the expert layers' load counts;
``train_steps.lfm2_loss_fn`` turns them into the next-token
cross-entropy on the embedding's own table.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.glm_moe import (
    ExpertLayer, SwiGLU, _dense, _keep_kernel_outputs, _norm,
)
from horovod_tpu.models.phi4flash import CausalDepthwiseConv
from horovod_tpu.models.qwen3next import best_grouped_attention
from horovod_tpu.models.transformer import apply_rope

# The published pattern: attention at 2, 6, ..., 38 of 40.
PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i % 4 == 2 else "conv" for i in range(40))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES   # by published index
    kept_layers: Optional[Tuple[int, ...]] = None    # None: all of them
    num_dense_layers: int = 2
    intermediate_size: int = 11776
    # attention; the head is hidden_size / num_heads
    num_heads: int = 32
    num_kv_heads: int = 8
    rope_theta: float = 1e6
    conv_L_cache: int = 3            # the short convolution's taps
    # the expert layer (what ``glm_moe.ExpertLayer`` reads)
    moe_intermediate_size: int = 1536
    shared_intermediate_size: int = 0    # no shared expert
    n_routed_experts: int = 64       # the router's width
    num_experts_per_tok: int = 4
    scoring: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    shared_expert_gate: bool = False
    topk_weight_eps: float = 1e-6
    n_group: int = 1
    topk_group: int = 1
    expert_activation: str = "silu"
    row_tier_headroom: float = 2.0
    # The share of the experts this chip holds: ids
    # [expert_offset, expert_offset + experts_held).
    experts_held: int = 64
    expert_offset: int = 0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def layers(self) -> Tuple[int, ...]:
        return tuple(range(len(self.layer_types))) \
            if self.kept_layers is None else tuple(self.kept_layers)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


class ShortConv(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d = cfg.hidden_size
        with jax.named_scope("shortconv.proj"):
            bcu = _dense(cfg, 3 * d, "in_proj")(x)
        with jax.named_scope("shortconv.conv"):
            # the gates and the taps in float32, one pass over the rows
            b, c, u = (bcu[..., i * d:(i + 1) * d].astype(jnp.float32)
                       for i in range(3))
            y = c * CausalDepthwiseConv(cfg.conv_L_cache, use_bias=False,
                                        name="conv")(b * u)
        with jax.named_scope("shortconv.proj"):
            return _dense(cfg, d, "out_proj")(y.astype(cfg.dtype))


class NormedGroupedAttention(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _norm(cfg, "q_norm")(_dense(cfg, (h, d), "q")(x))
        k = _norm(cfg, "k_norm")(_dense(cfg, (kv, d), "k")(x))
        v = _dense(cfg, (kv, d), "v")(x)
        out = best_grouped_attention(
            apply_rope(q, positions, cfg.rope_theta),
            apply_rope(k, positions, cfg.rope_theta), v)
        return _dense(cfg, cfg.hidden_size, "o", axis=(-2, -1))(out)


class Block(nn.Module):
    """``(x, counts)``: published layer ``index``."""

    cfg: Lfm2MoeConfig
    index: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h = _norm(cfg, "operator_norm")(x)
        if cfg.layer_types[self.index] == "conv":
            x = x + ShortConv(cfg, name="operator")(h)
        else:
            with jax.named_scope("normed_attn"):
                x = x + NormedGroupedAttention(cfg, name="operator")(
                    h, positions)
        h = _norm(cfg, "ffn_norm")(x)
        if self.index < cfg.num_dense_layers:
            y = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
            counts = jnp.zeros((cfg.experts_held + 2,), jnp.int32)
        else:
            y, counts = ExpertLayer(cfg, name="moe")(h)
        return x + y, counts


# Every block is recomputed in the backward pass: nine blocks'
# activations at 32,768 tokens do not fit a chip beside 10 GB of state.
RematBlock = nn.remat(Block, policy=_keep_kernel_outputs)


class Lfm2MoeLM(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, tokens):
        """tokens [B, S] -> ``(hidden, counts)``: the pre-head states
        [B, S, D] after the final norm (the head is the embedding's own
        table: training goes through ``lm_loss_from_hidden``, which
        never builds the logits) and the layers' counts
        [layers, experts_held + 2], a dense layer's as zeros."""
        cfg = self.cfg
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None], tokens.shape)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed")(tokens)
        counts = []
        for i in cfg.layers:
            x, c = RematBlock(cfg, i, name=f"layer_{i}")(x, positions)
            counts.append(c)
        return _norm(cfg, "norm_f")(x), jnp.stack(counts)
