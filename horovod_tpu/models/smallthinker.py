"""Local-global sparse decoder as SmallThinker-21BA3B (``smallthinker``)
lays it out: grouped-head attention that is windowed with a rotary in
three layers of four and full without any positional signal in the
fourth, and in every layer an expert layer whose router reads the
**block's input**, before attention, while its ReLU-gated experts read
the post-attention normalised states.

Pre-norm residual blocks, plain RMSNorm, no biases anywhere::

    r   = x W_r                       (the router's logits, float32)
    h   = x + attention(norm_a(x))
    out = h + experts(norm_f(h); r)

* **Attention** of **published** layer ``i``
  (:class:`LocalGlobalAttention`): q over ``num_heads``, k and v over
  ``num_kv_heads`` heads of ``head_dim``, no norm on q or k;
  ``rope_layout[i]`` 1: the rotary over the whole head, halves paired;
  0: no positions at all. ``sliding_window_layout[i]`` 1: a query sees
  its own key and the ``sliding_window_size - 1`` before it; 0: the
  whole prefix. The two layouts are read by index, each for itself.
  Each key-value head serves ``num_heads / num_kv_heads`` query heads
  through the flash kernels: nothing is repeated in HBM.
* **Experts**: ``glm_moe.ExpertLayer``, the one expert layer of every
  sparse model here, told that the scores are a softmax over all the
  experts with the chosen weights divided by their sum (which is the
  softmax over the chosen logits), that there is no shared expert, and
  that an expert is ``W_down (relu(W_gate u) * (W_up u))``. The block
  asks the layer for its routing of ``x`` (``ExpertLayer.route``: the
  choice, the gates, the order by expert and the row buffer's plan)
  **before** the attention call, so that none of it waits on the flash
  kernels in the traced program, and the router's gradient flows into
  the block's input.

The model may hold any subset of the published layers
(``kept_layers``); each keeps its published index, which fixes its
window and its rotary. Every block is recomputed in the backward pass
with its kernels' outputs kept. The model returns the pre-head states
after the final norm and the layers' load counts;
``train_steps.smallthinker_loss_fn`` turns them into the next-token
cross-entropy on an untied head.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.glm_moe import (
    ExpertLayer, _dense, _keep_kernel_outputs, _norm,
)
from horovod_tpu.models.qwen3next import best_grouped_attention
from horovod_tpu.models.transformer import apply_rope

# The published pattern, of the window and of the rotary alike: neither
# in layers 0, 4, ..., 48 of 52, both in every other.
PUBLISHED_LAYOUT = tuple(int(i % 4 != 0) for i in range(52))


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    # by published index: 1 a window (a rotary), 0 none
    sliding_window_layout: Tuple[int, ...] = PUBLISHED_LAYOUT
    rope_layout: Tuple[int, ...] = PUBLISHED_LAYOUT
    kept_layers: Optional[Tuple[int, ...]] = None    # None: all of them
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window_size: int = 4096
    rope_theta: float = 1.5e6
    # the expert layer (what ``glm_moe.ExpertLayer`` reads)
    moe_intermediate_size: int = 768
    shared_intermediate_size: int = 0    # no shared expert
    n_routed_experts: int = 64       # the router's width
    num_experts_per_tok: int = 6
    scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    shared_expert_gate: bool = False
    topk_weight_eps: float = 0.0
    n_group: int = 1
    topk_group: int = 1
    expert_activation: str = "relu"
    row_tier_headroom: float = 2.0
    # The share of the experts this chip holds: ids
    # [expert_offset, expert_offset + experts_held).
    experts_held: int = 64
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def layers(self) -> Tuple[int, ...]:
        return tuple(range(len(self.sliding_window_layout))) \
            if self.kept_layers is None else tuple(self.kept_layers)


class LocalGlobalAttention(nn.Module):
    """Published layer ``index``'s attention: its window and its rotary
    are what the two layouts say there."""

    cfg: SmallThinkerConfig
    index: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _dense(cfg, (h, d), "q")(x)
        k = _dense(cfg, (kv, d), "k")(x)
        v = _dense(cfg, (kv, d), "v")(x)
        if cfg.rope_layout[self.index]:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        window = cfg.sliding_window_size \
            if cfg.sliding_window_layout[self.index] else None
        out = best_grouped_attention(q, k, v, window)
        return _dense(cfg, cfg.hidden_size, "o", axis=(-2, -1))(out)


class Block(nn.Module):
    """``(x, counts)``: published layer ``index``."""

    cfg: SmallThinkerConfig
    index: int

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        moe = ExpertLayer(cfg, name="moe")
        # the router reads the block's input itself, ahead of both norms
        routing = moe.route(x, plan_ahead=True)
        attention = LocalGlobalAttention(cfg, self.index, name="attention")
        normed = _norm(cfg, "attention_norm")(x)
        if cfg.sliding_window_layout[self.index]:
            with jax.named_scope("swa_attn"):
                h = x + attention(normed, positions)
        else:
            with jax.named_scope("nope_attn"):
                h = x + attention(normed, positions)
        y, counts = moe(_norm(cfg, "ffn_norm")(h), routing)
        return h + y, counts


# Every block is recomputed in the backward pass: four blocks'
# activations at 32,768 tokens of 2,560 do not fit a chip beside 6.7 GB
# of state.
RematBlock = nn.remat(Block, policy=_keep_kernel_outputs)


class SmallThinkerLM(nn.Module):
    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, tokens):
        """tokens [B, S] -> ``(hidden, counts)``: the pre-head states
        [B, S, D] after the final norm (training goes through
        ``lm_loss_from_hidden``, which never builds the logits) and the
        layers' counts [layers, experts_held + 2]."""
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
