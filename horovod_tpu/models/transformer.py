"""Decoder-only Transformer LM — the flagship long-context model.

The reference has no model code of its own; this model exists so the
framework's parallelism extensions (tensor parallelism, sequence/ring
attention — horovod_tpu.parallel) have a first-class workload, and it
is the model behind ``__graft_entry__.py``.

TPU-first choices:
- bf16 activations/weights with fp32 softmax and layernorm statistics;
- pre-norm blocks, GELU MLP at 4x width (MXU-friendly 128-multiples);
- rotary position embeddings (no learned position table to shard);
- a pluggable ``attention_fn`` so sequence parallelism can substitute
  ring attention (horovod_tpu/parallel/ring_attention.py) without
  touching the module tree;
- no python-level control flow on data — the whole step jits to one
  XLA program.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 12
    head_dim: int = 64
    mlp_ratio: int = 4
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    rope_theta: float = 10000.0
    # attention_fn(q, k, v, causal) -> out; None = local causal attention.
    attention_fn: Optional[Callable] = None
    # Mixture-of-experts: 0 = dense MLP everywhere; E > 0 replaces the
    # MLP of every ``moe_every``-th block with a Switch-style top-1
    # MoE of E experts (expert parallelism: horovod_tpu.parallel
    # shards the leading expert dim over a mesh axis).
    num_experts: int = 0
    moe_every: int = 2
    expert_capacity_factor: float = 1.25
    moe_top_k: int = 1  # 1 = Switch; 2 = GShard-style top-2 gating

    @property
    def embed_dim(self) -> int:
        return self.num_heads * self.head_dim


def apply_rope(x, positions, theta: float = 10000.0):
    """Rotary embeddings. x: [B, S, H, D]; positions: [B, S]."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]  # [B,S,1,half]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


def causal_attention(q, k, v, causal: bool = True):
    """Plain fused-softmax causal attention. q,k,v: [B, S, H, D].
    fp32 logits/softmax, bf16 everywhere else."""
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(d))
    if causal:
        s = q.shape[1]
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def best_attention(q, k, v, causal: bool = True):
    """Default attention: the pallas flash kernel on TPU (O(S²) logits
    never touch HBM — horovod_tpu/parallel/flash_attention.py), dense
    fused-softmax elsewhere. Both produce identical math."""
    import jax
    if jax.default_backend() == "tpu" and causal:
        from horovod_tpu.parallel.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal)
    return causal_attention(q, k, v, causal)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        dense = lambda feats, name: nn.DenseGeneral(
            feats, axis=-1, use_bias=False, dtype=cfg.dtype, name=name)
        q = dense((cfg.num_heads, cfg.head_dim), "q")(x)
        k = dense((cfg.num_heads, cfg.head_dim), "k")(x)
        v = dense((cfg.num_heads, cfg.head_dim), "v")(x)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        attn = cfg.attention_fn or best_attention
        out = attn(q, k, v, True)
        return nn.DenseGeneral(cfg.embed_dim, axis=(-2, -1), use_bias=False,
                               dtype=cfg.dtype, name="o")(out)


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        hidden = cfg.mlp_ratio * cfg.embed_dim
        h = nn.Dense(hidden, use_bias=False, dtype=cfg.dtype, name="up")(x)
        h = nn.gelu(h)
        return nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                        name="down")(h)


class MoEMLP(nn.Module):
    """Switch-style top-1 mixture-of-experts MLP (the public
    GShard / Switch Transformer pattern): fp32 router, one-hot
    dispatch/combine einsums with a fixed per-expert capacity so the
    whole layer is static-shaped and jit-friendly. Expert weights
    carry a leading expert dimension that the sharding rules
    (parallel/sharding.py moe rules) place on a mesh axis — GSPMD then
    inserts the token all-to-alls that an NCCL-based expert-parallel
    implementation would hand-code. The load-balancing auxiliary term
    is sowed under ``intermediates/moe_aux`` (see
    ``moe_aux_loss``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        E = cfg.num_experts
        if cfg.moe_top_k > E:
            raise ValueError(
                f"moe_top_k={cfg.moe_top_k} exceeds num_experts={E}; "
                f"a token cannot be routed to more experts than exist")
        B, S, D = x.shape
        H = cfg.mlp_ratio * cfg.embed_dim
        # GShard-style token GROUPS (one per batch row): capacity and
        # the dispatch one-hots scale with S, not B*S, keeping the
        # layer's memory linear in the token count.
        C = max(1, int(cfg.expert_capacity_factor * S / E))

        # Router in fp32: softmax over experts must not quantize.
        gate_logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                               name="router")(x.astype(jnp.float32))
        probs = jax.nn.softmax(gate_logits, axis=-1)          # (B,S,E)

        # Top-k choice loop (k=1: Switch; k=2: GShard). Each choice
        # masks out the experts already chosen; gates renormalize over
        # the chosen set; capacity positions continue per expert across
        # choices (GShard's choice-major packing: all first choices
        # claim capacity before any second choice).
        left = probs
        onehots, gates = [], []
        for _ in range(max(1, cfg.moe_top_k)):
            idx = jnp.argmax(left, axis=-1)                   # (B,S)
            oh = jax.nn.one_hot(idx, E, dtype=jnp.float32)    # (B,S,E)
            onehots.append(oh)
            gates.append(jnp.sum(probs * oh, axis=-1))        # (B,S)
            left = left * (1.0 - oh)
        if cfg.moe_top_k > 1:
            # GShard renormalizes over the chosen pair; Switch (k=1)
            # keeps the raw router probability as the gate.
            denom = sum(gates) + 1e-9
            gates = [g / denom for g in gates]

        # Load-balance aux over the FIRST choice (the Switch term).
        self.sow("intermediates", "moe_aux",
                 E * jnp.sum(jnp.mean(onehots[0], axis=(0, 1))
                             * jnp.mean(probs, axis=(0, 1))))

        # Per-choice positions within each expert's capacity buffer
        # (per group); overflow tokens are dropped (contribute zero).
        disp = jnp.zeros(x.shape[:2] + (E, C), jnp.float32)   # (B,S,E,C)
        combine = jnp.zeros_like(disp)
        claimed = jnp.zeros(x.shape[:1] + (1, E), jnp.float32)  # (B,1,E)
        for oh, gate in zip(onehots, gates):
            pos = (jnp.cumsum(oh, axis=1) - 1.0 + claimed) * oh
            keep = ((pos >= 0) & (pos < C)).astype(jnp.float32) * oh
            choice_disp = jax.nn.one_hot(
                pos.astype(jnp.int32), C, dtype=jnp.float32) \
                * keep[..., None]
            disp = disp + choice_disp
            combine = combine + choice_disp * gate[..., None, None]
            claimed = claimed + jnp.sum(oh, axis=1, keepdims=True)

        expert_in = jnp.einsum("bsec,bsd->becd",
                               disp.astype(cfg.dtype),
                               x.astype(cfg.dtype))           # (B,E,C,D)
        w1 = self.param("w1", nn.initializers.lecun_normal(),
                        (E, D, H), jnp.float32).astype(cfg.dtype)
        w2 = self.param("w2", nn.initializers.lecun_normal(),
                        (E, H, D), jnp.float32).astype(cfg.dtype)
        h = nn.gelu(jnp.einsum("becd,edh->bech", expert_in, w1))
        expert_out = jnp.einsum("bech,ehd->becd", h, w2)      # (B,E,C,D)

        return jnp.einsum("bsec,becd->bsd", combine.astype(cfg.dtype),
                          expert_out)


def moe_aux_loss(intermediates) -> jnp.ndarray:
    """Sum of the sowed Switch load-balancing terms; add
    ``alpha * moe_aux_loss(...)`` (alpha ~ 0.01) to the task loss when
    training MoE configs (apply with ``mutable=['intermediates']``)."""
    leaves = [v for path, v in
              jax.tree_util.tree_flatten_with_path(intermediates)[0]
              if "moe_aux" in "/".join(str(p) for p in path)]
    total = jnp.zeros((), jnp.float32)
    for leaf in leaves:
        total = total + jnp.sum(jnp.asarray(leaf))
    return total


class Block(nn.Module):
    cfg: TransformerConfig
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        ln = lambda name: nn.LayerNorm(use_bias=False, use_scale=True,
                                       dtype=cfg.dtype, name=name,
                                       param_dtype=jnp.float32)
        x = x + Attention(cfg, name="attn")(ln("ln1")(x), positions)
        if self.use_moe:
            x = x + MoEMLP(cfg, name="moe")(ln("ln2")(x))
        else:
            x = x + MLP(cfg, name="mlp")(ln("ln2")(x))
        return x


class TransformerLM(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None, return_hidden=False):
        """tokens: [B, S] int32 → logits [B, S, vocab] fp32.

        ``return_hidden=True`` returns the pre-head hidden states
        [B, S, D] (after ln_f, cfg.dtype) instead — the input to
        :func:`lm_loss_from_hidden`'s chunked cross-entropy, which
        avoids ever materializing the full [B, S, vocab] fp32 logits
        (multi-GB at vocab 32k and long context). XLA dead-code
        eliminates the unbuilt head."""
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32)[None],
                tokens.shape)
        x = nn.Embed(cfg.vocab_size, cfg.embed_dim, dtype=cfg.dtype,
                     name="embed")(tokens)
        for i in range(cfg.num_layers):
            use_moe = (cfg.num_experts > 0
                       and i % cfg.moe_every == cfg.moe_every - 1)
            x = Block(cfg, use_moe=use_moe, name=f"block_{i}")(
                x, positions)
        x = nn.LayerNorm(use_bias=False, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="ln_f")(x)
        if return_hidden:
            return x
        logits = nn.Dense(cfg.vocab_size, use_bias=False,
                          dtype=jnp.float32, name="lm_head")(
                              x.astype(jnp.float32))
        return logits


def lm_loss(logits, tokens):
    """Next-token cross-entropy, mean over all predicted positions."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def _chunked(hidden, tokens, chunk: int):
    """The head loss's layout: hidden states, targets and the padding
    mask as ``[chunks, B, chunk, ...]``, with the batch, the predicted
    positions and the chunk length the scan really takes."""
    targets = tokens[:, 1:]
    hid = hidden[:, :-1]
    b, s, d = hid.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    mask = jnp.ones((b, s), jnp.float32)
    if pad:
        hid = jnp.pad(hid, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (s + pad) // chunk
    hid = hid.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    targets = targets.reshape(b, n, chunk).transpose(1, 0, 2)
    mask = mask.reshape(b, n, chunk).transpose(1, 0, 2)
    return (hid, targets, mask), b, s, chunk


def _chunk_ll(h, head_kernel, t):
    """A chunk's targets' log-likelihoods and all its log-probabilities
    (float32; ``log_softmax``'s arithmetic). The targets are picked from
    the logits themselves, so that nothing but the logits has to exist
    as an array: the log-probabilities fuse into whatever reads them."""
    logits = h.astype(jnp.float32) @ head_kernel
    top = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1, keepdims=True))
    ll = jnp.take_along_axis(logits, t[..., None], axis=-1) - top - lse
    return ll[..., 0], logits - top - lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def lm_loss_from_hidden(hidden, head_kernel, tokens, chunk: int = 1024):
    """Chunked next-token cross-entropy from pre-head hidden states.

    Identical math to ``lm_loss(model(tokens), tokens)`` but the
    [B, S, vocab] fp32 logits are never materialized: the head matmul
    + log-softmax run per sequence chunk inside a scan, so peak logits
    memory is B × chunk × vocab. At vocab 32k, seq 4096, batch 8 this
    turns 2 × 3.9 GB of fp32 logits buffers into 2 × ~1 GB at
    chunk=1024 (scaling linearly in chunk).

    **A differentiated call makes its gradient in the pass that makes
    the loss** (``jax.custom_vjp``): everything the cross-entropy's
    gradient needs is known once a chunk's logits are, so the same scan
    forms ``dlogits = (onehot - softmax) * mask * -1/(B*S)``, emits the
    chunk's ``dlogits @ W^T`` in the hidden's type and adds ``h^T @
    dlogits`` into a float32 [D, vocab] carry: three vocabulary-sized
    products a chunk, one softmax, and the backward pass only scales the
    two kept gradients by the loss's cotangent. (Autodiff of the
    rematerialized scan recomputed each chunk's logits and softmax in
    the backward: four products.) An undifferentiated call forms no
    gradient. The gradient of the table is always formed, so a caller
    that froze the table would pay for a product it drops; reverse mode
    once is what there is (no ``jvp``, no gradient of the gradient).

    hidden: [B, S, D] as returned by ``model(tokens,
    return_hidden=True)``; head_kernel: the lm_head kernel
    ``params["lm_head"]["kernel"]`` [D, vocab] fp32, or a tied
    embedding's transpose.
    """
    xs, b, s, _ = _chunked(hidden, tokens, chunk)

    def body(total, x):
        h, t, m = x
        return total + jnp.sum(_chunk_ll(h, head_kernel, t)[0] * m), None

    with jax.named_scope("lm_head_loss"):
        total, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
    return -total / (b * s)


def _lm_loss_fwd(hidden, head_kernel, tokens, chunk):
    xs, b, s, chunk = _chunked(hidden, tokens, chunk)
    vocab = head_kernel.shape[-1]

    def body(carry, x):
        total, d_kernel = carry
        h, t, m = x
        ll, logp = _chunk_ll(h, head_kernel, t)
        d_logits = ((jax.nn.one_hot(t, vocab, dtype=jnp.float32)
                     - jnp.exp(logp)) * (m * (-1.0 / (b * s)))[..., None])
        # the two products autodiff makes of ``h.astype(f32) @ W``, at
        # its operand types and default precision
        d_h = jnp.einsum("bcv,dv->bcd", d_logits, head_kernel)
        d_kernel = d_kernel + jnp.einsum(
            "bcd,bcv->dv", h.astype(jnp.float32), d_logits)
        return (total + jnp.sum(ll * m), d_kernel), d_h.astype(h.dtype)

    # the body's three products: the logits, ``d_h`` and ``d_kernel``
    # (docs/metrics.md)
    from horovod_tpu.common import basics
    basics.note_traced(
        "hvd_head_loss_chunks",
        "the differentiated head loss traced last: chunks a sequence, "
        "positions a chunk and vocabulary-sized products a chunk",
        {"chunks": xs[0].shape[0], "chunk_length": chunk, "products": 3})
    with jax.named_scope("lm_head_loss"):
        (total, d_kernel), d_hid = jax.lax.scan(
            body, (jnp.float32(0.0),
                   jnp.zeros(head_kernel.shape, jnp.float32)), xs)
    # back through ``_chunked``: positions in order, the padding and the
    # last position (which predicts nothing) without a gradient
    d_hid = d_hid.transpose(1, 0, 2, 3).reshape(b, -1, hidden.shape[-1])
    d_hidden = jnp.pad(d_hid[:, :s], ((0, 0), (0, 1), (0, 0)))
    return -total / (b * s), (d_hidden, d_kernel.astype(head_kernel.dtype))


def _lm_loss_bwd(chunk, kept, g):
    d_hidden, d_kernel = kept
    return ((d_hidden * g).astype(d_hidden.dtype),
            (d_kernel * g).astype(d_kernel.dtype), None)


lm_loss_from_hidden.defvjp(_lm_loss_fwd, _lm_loss_bwd)
