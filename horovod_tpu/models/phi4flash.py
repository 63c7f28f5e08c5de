"""Decoder-hybrid-decoder (SambaY, arXiv:2507.06607) as
Phi-4-mini-flash-reasoning (``phi4flash``) lays it out: a self-decoder
of Mamba and windowed differential attention, one full-attention layer
whose keys and values are kept, and a cross-decoder that reads that
one key-value pair and one scan memory through all its layers.

Pre-norm residual blocks, LayerNorm with bias, no positional signal of
any kind, a gated feed-forward with no biases, the embedding tied to
the head::

    h   = x + mixer(LN(x))
    out = h + fc2(up * silu(gate)),   [gate, up] = fc1(LN(h))

The mixer of **published** layer ``i`` of ``L`` (``layer_kind``):

* ``i`` even, ``i <= L/2``: **Mamba** (:class:`Mamba`; Mamba-1,
  arXiv:2312.00752): ``[u, z] = x W_in``; a causal depthwise
  convolution and SiLU on ``u``; ``[r, B, C] = u W_x``; ``delta =
  softplus(r W_dt + b)`` in float32; the selective scan
  (``parallel.ssm_scan``: state in float32); ``out = (y silu(z))
  W_out``. Layer ``L/2`` also hands out its **memory** ``y``, the scan's
  output before the gate.
* ``i`` odd, ``i < L/2``: **differential attention** (arXiv:2410.05258)
  inside a window; ``i = L/2 + 1``: the same over the whole prefix,
  and its ``k`` and ``v`` are kept. Query heads come in pairs ``(q1,
  q2)``, key heads in pairs ``(k1, k2)``, each key pair beside one
  value head of twice the size; with ``a_j = softmax(q_j k_j^T /
  sqrt(d) + mask) v``::

      o = RMSNorm(a1 - lambda a2) (1 - lambda_init)
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
      lambda_init = 0.8 - 0.6 exp(-0.3 i)

  Two flash calls a layer, each over fewer key-value heads than query
  heads and a value head wider than the key head; nothing is repeated
  in HBM.
* ``i`` even, ``i >= L/2 + 2``: **gated memory unit**: ``out = (M
  silu(x W_1)) W_2`` with ``M`` layer ``L/2``'s memory.
* ``i`` odd, ``i >= L/2 + 3``: **differential cross-attention**: its
  own ``q = x W_q`` against layer ``L/2 + 1``'s ``k`` and ``v``.

The model may hold any subset of the published layers
(``kept_layers``); each keeps its published index, which fixes its
kind and its ``lambda_init``. Every block is recomputed in the backward
pass with its kernels' outputs kept; the memory and the key-value pair
travel from block to block beside the residual, so the gradients of
all their readers add into them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.glm_moe import _keep_kernel_outputs
from horovod_tpu.parallel.ssm_scan import selective_scan


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_heads: int = 40
    num_kv_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    published_layers: int = 32       # fixes every layer's kind
    kept_layers: Optional[Tuple[int, ...]] = None    # None: all of them
    # Mamba-1's sizes (the family's defaults at this width)
    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    # Added to dt_proj's learnt bias before the softplus. 0 for trained
    # weights (their bias holds it); a job that starts from weights whose
    # biases are all zero gives Mamba's starting point here, softplus^-1
    # of a step in [0.001, 0.1]: the same function family and the same
    # gradients as a bias initialised there.
    dt_bias_init: float = 0.0
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # (q, k, v, window) -> [B, S, H, Dv] float32; None: the flash kernels
    # on the TPU, the dense formulation elsewhere
    attention_fn: Optional[Callable] = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def layers(self) -> Tuple[int, ...]:
        return tuple(range(self.published_layers)) \
            if self.kept_layers is None else tuple(self.kept_layers)


def layer_kind(index: int, published_layers: int) -> str:
    """``mamba``, ``window``, ``full``, ``gmu`` or ``cross``: the mixer
    of published layer ``index``."""
    half = published_layers // 2
    if index % 2 == 0:
        return "mamba" if index <= half else "gmu"
    if index < half:
        return "window"
    return "full" if index == half + 1 else "cross"


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def best_windowed_attention(q, k, v, window=None):
    """The flash kernels on the TPU, the same mathematics dense
    elsewhere. q: [B,S,H,D]; k: [B,S,Hkv,D]; v: [B,S,Hkv,Dv]; the
    result in float32, as the kernels accumulate it: differential
    attention subtracts two such maps that are nearly equal, and a
    rounding to bfloat16 first leaves a twentieth of lambda's gradient
    to it (PERF.md, PR 31)."""
    from horovod_tpu.parallel import flash_attention as fa
    if jax.default_backend() == "tpu":
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  out_dtype=jnp.float32)
    return fa._dense_reference(q, k, v, True, 0, 0, window, jnp.float32)


def _ln(cfg: Phi4FlashConfig, name: str):
    return nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                        param_dtype=jnp.float32, name=name)


def _dense(cfg: Phi4FlashConfig, features: int, name: str, bias=False,
           dtype=None):
    return nn.Dense(features, use_bias=bias, dtype=dtype or cfg.dtype,
                    name=name)


class CausalDepthwiseConv(nn.Module):
    """``out_t = sum_j w[j] x_{t - (taps - 1) + j} + b`` a channel, in
    float32: position ``t`` reads itself and the ``taps - 1`` before.
    ``use_bias`` False leaves ``b`` out (the Gated DeltaNet's
    convolution has none)."""

    taps: int
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        seq, channels = x.shape[1], x.shape[2]
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (self.taps, channels), jnp.float32)
        padded = jnp.pad(x, ((0, 0), (self.taps - 1, 0), (0, 0)))
        out = sum(padded[:, j:j + seq].astype(jnp.float32) * kernel[j]
                  for j in range(self.taps))
        if not self.use_bias:
            return out
        return out + self.param("bias", nn.initializers.zeros, (channels,),
                                jnp.float32)


class Mamba(nn.Module):
    """``(out, memory)``: the block's output and the scan's output
    before the gate, [B, S, d_inner]."""

    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        di, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
        with jax.named_scope("ssm.proj"):
            uz = _dense(cfg, 2 * di, "in_proj")(x)
            u, z = uz[..., :di], uz[..., di:]
        with jax.named_scope("ssm.conv"):
            u = nn.silu(CausalDepthwiseConv(cfg.d_conv, name="conv")(u)) \
                .astype(cfg.dtype)
        with jax.named_scope("ssm.proj"):
            rbc = _dense(cfg, r + 2 * n, "x_proj")(u)
            # float32 from here: delta multiplies A in an exponent
            delta = jax.nn.softplus(_dense(
                cfg, di, "dt_proj", bias=True, dtype=jnp.float32)(
                    rbc[..., :r]) + cfg.dt_bias_init)
            b = rbc[..., r:r + n].astype(jnp.float32)
            c = rbc[..., r + n:].astype(jnp.float32)
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(jnp.broadcast_to(
                jnp.arange(1, n + 1, dtype=jnp.float32), shape)), (di, n))
        skip = self.param("D", nn.initializers.ones, (di,), jnp.float32)
        with jax.named_scope("ssm.scan"):
            y = selective_scan(u, delta, -jnp.exp(a_log), b, c, skip)
        with jax.named_scope("ssm.gate"):
            gated = y * nn.silu(z)
        with jax.named_scope("ssm.proj"):
            return _dense(cfg, cfg.hidden_size, "out_proj")(gated), y


class DifferentialAttention(nn.Module):
    """``(out, k, v)``. Self-attention (``kv`` None) projects q, k and
    v from ``x``; cross-attention projects q alone and reads ``kv``,
    the pair another layer kept. k: [B, S, pairs, 2, D]; v: [B, S,
    pairs, 2 D]."""

    cfg: Phi4FlashConfig
    index: int                       # the published layer index
    window: Optional[int] = None

    @nn.compact
    def __call__(self, x, kv=None):
        cfg = self.cfg
        d, pairs = cfg.head_dim, cfg.num_heads // 2
        kv_pairs = cfg.num_kv_heads // 2
        lead = x.shape[:2]
        if kv is None:
            qkv = _dense(cfg, (cfg.num_heads + 2 * cfg.num_kv_heads) * d,
                         "qkv", bias=True)(x)
            q = qkv[..., :cfg.num_heads * d]
            k = qkv[..., cfg.num_heads * d:
                    (cfg.num_heads + cfg.num_kv_heads) * d] \
                .reshape(*lead, kv_pairs, 2, d)
            v = qkv[..., (cfg.num_heads + cfg.num_kv_heads) * d:] \
                .reshape(*lead, kv_pairs, 2 * d)
        else:
            q = _dense(cfg, cfg.num_heads * d, "q", bias=True)(x)
            k, v = kv
        q = q.reshape(*lead, pairs, 2, d)
        attend = cfg.attention_fn or best_windowed_attention
        a1 = attend(q[..., 0, :], k[..., 0, :], v, self.window)
        a2 = attend(q[..., 1, :], k[..., 1, :], v, self.window)

        vec = lambda name: self.param(
            name, nn.initializers.normal(0.1), (d,), jnp.float32)
        init = lambda_init(self.index)
        lam = jnp.exp(jnp.sum(vec("lambda_q1") * vec("lambda_k1"))) \
            - jnp.exp(jnp.sum(vec("lambda_q2") * vec("lambda_k2"))) + init
        diff = a1.astype(jnp.float32) - lam * a2.astype(jnp.float32)
        normed = nn.RMSNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                            param_dtype=jnp.float32, name="subln")(diff) \
            * (1.0 - init)
        out = _dense(cfg, cfg.hidden_size, "out_proj", bias=True)(
            normed.astype(cfg.dtype).reshape(*lead, pairs * 2 * d))
        return out, k, v


class GatedMemoryUnit(nn.Module):
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, memory):
        cfg = self.cfg
        gate = nn.silu(_dense(cfg, cfg.d_inner, "in_proj")(x))
        return _dense(cfg, cfg.hidden_size, "out_proj")(memory * gate)


class Block(nn.Module):
    """``(x, memory, k, v)`` in and out: the residual, and beside it
    what the cross-decoder's layers read. A layer that makes the memory
    or the key-value pair puts it there; every other hands both on as
    it got them (``None`` before they exist)."""

    cfg: Phi4FlashConfig
    index: int

    @nn.compact
    def __call__(self, x, memory, k, v):
        cfg = self.cfg
        kind = layer_kind(self.index, cfg.published_layers)
        h = _ln(cfg, "ln1")(x)
        if kind == "mamba":
            mixed, y = Mamba(cfg, name="mixer")(h)
            if self.index == cfg.published_layers // 2:
                memory = y
        elif kind == "gmu":
            with jax.named_scope("gmu"):
                mixed = GatedMemoryUnit(cfg, name="mixer")(h, memory)
        else:
            scope = {"window": "diff_attn.window", "full": "diff_attn",
                     "cross": "diff_attn.cross"}[kind]
            with jax.named_scope(scope):
                mixed, k_own, v_own = DifferentialAttention(
                    cfg, self.index,
                    cfg.sliding_window if kind == "window" else None,
                    name="mixer")(h, (k, v) if kind == "cross" else None)
            if kind == "full":
                k, v = k_own, v_own
        x = x + mixed
        h = _ln(cfg, "ln2")(x)
        with jax.named_scope("mlp"):
            gate_up = _dense(cfg, 2 * cfg.intermediate_size, "fc1")(h)
            width = cfg.intermediate_size
            x = x + _dense(cfg, cfg.hidden_size, "fc2")(
                gate_up[..., width:] * nn.silu(gate_up[..., :width]))
        return x, memory, k, v


# Every block is recomputed in the backward pass: six blocks'
# activations at 16,384 tokens do not fit a chip beside 9.1 GB of state.
RematBlock = nn.remat(Block, policy=_keep_kernel_outputs)


class Phi4FlashLM(nn.Module):
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, tokens, return_logits: bool = False):
        """tokens [B, S] -> the pre-head states [B, S, D] after the
        final norm (training goes through ``lm_loss_from_hidden`` on
        the embedding's transpose, which never builds the logits), or
        with ``return_logits`` the float32 logits [B, S, vocab]."""
        cfg = self.cfg
        kinds = [layer_kind(i, cfg.published_layers) for i in cfg.layers]
        half = cfg.published_layers // 2
        if ("gmu" in kinds and half not in cfg.layers) or \
                ("cross" in kinds and half + 1 not in cfg.layers):
            raise ValueError(
                f"layers {cfg.layers} read a memory or a key-value pair "
                f"that layers {half} and {half + 1} would make")
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="embed")
        x = embed(tokens)
        memory = k = v = None
        for i in cfg.layers:
            x, memory, k, v = RematBlock(cfg, i, name=f"layer_{i}")(
                x, memory, k, v)
        hidden = _ln(cfg, "norm_f")(x)
        if return_logits:
            return hidden.astype(jnp.float32) @ embed.embedding.T
        return hidden
