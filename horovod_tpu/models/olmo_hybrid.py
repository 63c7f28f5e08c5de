"""Dense hybrid decoder as Olmo-Hybrid-7B (``olmo_hybrid``) lays it
out: Gated DeltaNet in three layers of four, full softmax attention
with no positional signal in the fourth, a dense SwiGLU in every one.

Residual blocks whose norms sit on the **outputs** of the mixer and of
the feed-forward (the Olmo 2 and Olmo 3 convention), plain RMSNorm with
a learnt scale, no biases anywhere, no norm on a sub-layer's input::

    h   = x + norm_a(mixer(x))
    out = h + norm_f(mlp(h))

The mixer of **published** layer ``i`` is what ``layer_types[i]`` says.

* ``linear_attention``: ``qwen3next.GatedDeltaNet``, the one Gated
  DeltaNet (arXiv:2412.06464) of both models, told by this
  configuration that a key head of 96 serves one value head of 192
  (heads off the lane tile: they travel laid out between the
  projections, ``parallel.gated_delta.lay_heads``) and that ``beta = 2
  sigmoid(b)`` (``linear_allow_neg_eigval``: the state's transition ``I
  - beta k k^T`` has an eigenvalue in (-1, 1), and the rule's kernels
  take the chunk's inverse by blocks).
* ``full_attention`` (:class:`FullAttention`): ``q, k, v = x W_q, x
  W_k, x W_v``; an RMSNorm over the **whole projection** on q and on k
  (a scale of ``hidden_size`` each); **no rotary and no other
  positional signal** (the linear layers carry order); causal softmax
  attention a head of ``hidden_size / num_heads`` through the flash
  kernels; ``out = a W_o``. No gate, no window.
* ``mlp``: ``glm_moe.SwiGLU`` of ``intermediate_size``.

The model may hold any subset of the published layers
(``kept_layers``); each keeps its published index, which fixes its
mixer. Every block is recomputed in the backward pass with its kernels'
outputs kept (the rule's ``o`` and entering states, flash attention's
output and row statistics). The model returns the pre-head states after
the final norm; ``train_steps.olmo_hybrid_loss_fn`` turns them into the
next-token cross-entropy on an untied head.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.glm_moe import (
    SwiGLU, _dense, _keep_kernel_outputs, _norm,
)
from horovod_tpu.models.qwen3next import GatedDeltaNet, best_grouped_attention

# The published pattern: full attention at 3, 7, ..., 31 of 32.
PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i % 4 == 3 else "linear_attention"
    for i in range(32))


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES   # by published index
    kept_layers: Optional[Tuple[int, ...]] = None    # None: all of them
    intermediate_size: int = 11008
    # full attention; the head is hidden_size / num_heads
    num_heads: int = 30
    # Gated DeltaNet (what ``qwen3next.GatedDeltaNet`` reads)
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # added to the learnt A_log and dt_bias: ``Qwen3NextConfig`` says why
    a_log_init: float = 0.0
    dt_bias_init: float = 0.0
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def layers(self) -> Tuple[int, ...]:
        return tuple(range(len(self.layer_types))) \
            if self.kept_layers is None else tuple(self.kept_layers)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


class FullAttention(nn.Module):
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        heads = (*x.shape[:2], cfg.num_heads, cfg.head_dim)
        d = cfg.hidden_size
        q = _norm(cfg, "q_norm")(_dense(cfg, d, "q")(x))
        k = _norm(cfg, "k_norm")(_dense(cfg, d, "k")(x))
        v = _dense(cfg, d, "v")(x)
        out = best_grouped_attention(
            q.reshape(heads), k.reshape(heads), v.reshape(heads))
        return _dense(cfg, d, "o")(out.reshape(x.shape))


class Block(nn.Module):
    """Published layer ``index``."""

    cfg: OlmoHybridConfig
    index: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        if cfg.layer_types[self.index] == "linear_attention":
            y = GatedDeltaNet(cfg, name="mixer")(x)
        else:
            with jax.named_scope("normed_attn"):
                y = FullAttention(cfg, name="mixer")(x)
        with jax.named_scope("post_norm"):
            x = x + _norm(cfg, "mixer_norm")(y)
        y = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(x)
        with jax.named_scope("post_norm"):
            return x + _norm(cfg, "mlp_norm")(y)


# Every block is recomputed in the backward pass: four blocks'
# activations at 8,192 tokens and a feed-forward of 11,008 do not fit a
# chip beside 11.1 GB of state.
RematBlock = nn.remat(Block, policy=_keep_kernel_outputs)


class OlmoHybridLM(nn.Module):
    cfg: OlmoHybridConfig

    @nn.compact
    def __call__(self, tokens):
        """tokens [B, S] -> the pre-head states [B, S, D] after the
        final norm (training goes through ``lm_loss_from_hidden``, which
        never builds the logits)."""
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed")(tokens)
        for i in cfg.layers:
            x = RematBlock(cfg, i, name=f"layer_{i}")(x)
        hidden = _norm(cfg, "norm_f")(x)
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")
        if self.is_initializing():      # creates the head's parameters
            head(hidden.astype(jnp.float32))
        return hidden
