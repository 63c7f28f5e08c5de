"""Ring attention — sequence/context parallelism over a mesh axis.

Absent from the reference (SURVEY §5: it scales batch, never sequence);
first-class here because long-context is where TPU pods shine. The
sequence is sharded across the ``seq`` mesh axis; each device computes
blockwise attention for its query shard while key/value shards rotate
around the ring via ``jax.lax.ppermute``, accumulating with an online
(flash-style) softmax. Peak memory per device is O(S/p · S/p) for the
logits block instead of O(S²); the p permute steps ride ICI
neighbour-to-neighbour links, the cheapest traffic on a torus.

Causality is positional: block t of the ring carries keys whose global
positions derive from their source shard, so the mask is exact and the
result is bit-for-bit the same math as single-device causal attention
(up to fp32 accumulation order).
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from horovod_tpu.compat import jaxshim


def _block_attend(q, k, v, q_pos, k_pos, o, m, l, causal):
    """One blockwise online-softmax update.

    q: [B,Sq,H,D]; k,v: [B,Sk,H,D]; q_pos: [Sq]; k_pos: [Sk]
    o: [B,Sq,H,D] fp32 accumulator; m,l: [B,H,Sq] fp32 running max/sum.
    """
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(d))
    if causal:
        allowed = q_pos[:, None] >= k_pos[None, :]          # [Sq,Sk]
        logits = jnp.where(allowed[None, None], logits, -jnp.inf)
    block_max = jnp.max(logits, axis=-1)                     # [B,H,Sq]
    m_new = jnp.maximum(m, block_max)
    # Fully-masked blocks give m_new == -inf; guard the exp shift.
    shift = jnp.where(jnp.isinf(m_new), 0.0, m_new)
    p = jnp.exp(logits - shift[..., None])                   # [B,H,Sq,Sk]
    if causal:
        p = jnp.where(allowed[None, None], p, 0.0)
    corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - shift))
    # First contribution: m == -inf => corr 0 discards the zero state.
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return o_new, m_new, l_new


def _ring_einsum(q, k, v, causal: bool, axis: str):
    """Reference ring implementation: jax-level blockwise online
    softmax. Exact; also the differentiation target for the flash
    path's custom VJP."""
    p = jaxshim.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    b, s_local, h, d = q.shape

    q_pos = idx * s_local + jnp.arange(s_local)

    o = jnp.zeros((b, s_local, h, d), jnp.float32)
    m = jnp.full((b, h, s_local), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, s_local), jnp.float32)

    perm = [(i, (i - 1) % p) for i in range(p)]  # shift blocks backwards

    def step(t, carry):
        k_t, v_t, o_t, m_t, l_t = carry
        src = (idx + t) % p                       # owner of current kv
        k_pos = src * s_local + jnp.arange(s_local)
        o_t, m_t, l_t = _block_attend(q, k_t, v_t, q_pos, k_pos,
                                      o_t, m_t, l_t, causal)
        k_n = jax.lax.ppermute(k_t, axis, perm)
        v_n = jax.lax.ppermute(v_t, axis, perm)
        return k_n, v_n, o_t, m_t, l_t

    if p == 1:
        _, _, o, m, l = step(0, (k, v, o, m, l))
    else:
        k_c, v_c, o, m, l = jax.lax.fori_loop(
            0, p, step, (k, v, o, m, l))
    denom = jnp.where(l == 0.0, 1.0, l).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def _ring_flash_fwd_impl(q, k, v, causal: bool, axis: str, block: int):
    """Ring forward where each local block runs the pallas flash
    kernel (flash_attention_stats) and the per-shard (o, m, l) softmax
    statistics are merged across ring steps. kv rotation and merge
    live at the jax level (ppermute on ICI); the O(S_local²) inner
    work never leaves VMEM. Returns (o, m, l) — the merged global
    stats are the backward's residuals."""
    from horovod_tpu.parallel.flash_attention import flash_attention_stats

    p = jaxshim.axis_size(axis)
    b, s_local, h, d = q.shape
    # Only the causal mask reads the positions. Without it the offsets
    # are dead code, and a dead axis_index inside the fori_loop body is
    # hoisted out of the shard_map manual region, where the 0.4.x SPMD
    # partitioner rejects the orphaned partition-id instruction.
    idx = jax.lax.axis_index(axis) if causal else jnp.int32(0)
    q_off = idx * s_local

    o_num = jnp.zeros((b, s_local, h, d), jnp.float32)
    m_run = jnp.full((b, h, s_local), -1e30, jnp.float32)
    l_run = jnp.zeros((b, h, s_local), jnp.float32)

    perm = [(i, (i - 1) % p) for i in range(p)]

    def step(t, carry):
        k_t, v_t, o_num, m_run, l_run = carry
        src = (idx + t) % p
        o_i, m_i, l_i = flash_attention_stats(
            q, k_t, v_t, causal=causal, q_offset=q_off,
            k_offset=src * s_local, block_q=block, block_k=block)
        m_new = jnp.maximum(m_run, m_i)
        a = jnp.exp(m_run - m_new)
        c = jnp.exp(m_i - m_new)
        w = (l_i * c).transpose(0, 2, 1)[..., None]     # [B,S,H,1]
        o_num = o_num * a.transpose(0, 2, 1)[..., None] \
            + o_i.astype(jnp.float32) * w
        l_run = l_run * a + l_i * c
        k_n = jax.lax.ppermute(k_t, axis, perm)
        v_n = jax.lax.ppermute(v_t, axis, perm)
        return k_n, v_n, o_num, m_new, l_run

    if p == 1:
        _, _, o_num, m_run, l_run = step(0, (k, v, o_num, m_run, l_run))
    else:
        _, _, o_num, m_run, l_run = jax.lax.fori_loop(
            0, p, step, (k, v, o_num, m_run, l_run))
    denom = jnp.where(l_run == 0.0, 1.0,
                      l_run).transpose(0, 2, 1)[..., None]
    return (o_num / denom).astype(q.dtype), m_run, l_run


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, causal, axis, block):
    return _ring_flash_fwd_impl(q, k, v, causal, axis, block)[0]


def _ring_flash_fwd(q, k, v, causal, axis, block):
    o, m, l = _ring_flash_fwd_impl(q, k, v, causal, axis, block)
    return o, (q, k, v, o, m, l)


def _ring_flash_bwd(causal, axis, block, residuals, g):
    """Ring backward on the pallas backward kernels: a second kv pass
    where each rotated shard's (dk, dv) accumulators travel with it —
    after p rotations they arrive back at the owning device. Per-shard
    contributions use the globally-merged lse, so their sum is the
    exact full-sequence gradient (same math as the dense backward, up
    to fp32 accumulation order)."""
    from horovod_tpu.parallel.flash_attention import (
        _flash_bwd_bhsd, _lse_from_stats, _to_bhsd, _from_bhsd,
    )

    q, k, v, o, m, l = residuals
    p = jaxshim.axis_size(axis)
    b, s_local, h, d = q.shape
    # See _ring_flash_fwd_impl: keep axis_index out of the trace when
    # the causal mask (its only consumer) is off.
    idx = jax.lax.axis_index(axis) if causal else jnp.int32(0)
    q_off = idx * s_local
    perm = [(i, (i - 1) % p) for i in range(p)]
    interpret = jax.default_backend() != "tpu"

    # Loop-invariant residual prep, done once: layout transposes of the
    # local tensors, lse from the merged stats, delta = rowsum(do·o).
    qb, gb, ob = _to_bhsd(q), _to_bhsd(g), _to_bhsd(o)
    kb, vb = _to_bhsd(k), _to_bhsd(v)
    lse = _lse_from_stats(m, l)
    delta = jnp.sum(gb.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)[:, None, :]   # [BH,1,S], see _lse_from_stats

    dq0 = jnp.zeros(qb.shape, jnp.float32)
    dk0 = jnp.zeros(kb.shape, jnp.float32)
    dv0 = jnp.zeros(vb.shape, jnp.float32)

    def step(t, carry):
        k_t, v_t, dk_t, dv_t, dq = carry
        src = (idx + t) % p
        offsets = jnp.stack([jnp.asarray(q_off, jnp.int32),
                             jnp.asarray(src * s_local, jnp.int32)])
        dq_i, dk_i, dv_i = _flash_bwd_bhsd(
            qb, k_t, v_t, gb, lse, delta, offsets, causal, block,
            block, interpret)
        dq = dq + dq_i.astype(jnp.float32)
        dk_t = dk_t + dk_i.astype(jnp.float32)
        dv_t = dv_t + dv_i.astype(jnp.float32)
        k_n = jax.lax.ppermute(k_t, axis, perm)
        v_n = jax.lax.ppermute(v_t, axis, perm)
        dk_n = jax.lax.ppermute(dk_t, axis, perm)
        dv_n = jax.lax.ppermute(dv_t, axis, perm)
        return k_n, v_n, dk_n, dv_n, dq

    if p == 1:
        _, _, dk, dv, dq = step(0, (kb, vb, dk0, dv0, dq0))
    else:
        _, _, dk, dv, dq = jax.lax.fori_loop(
            0, p, step, (kb, vb, dk0, dv0, dq0))
    return (_from_bhsd(dq, b, h).astype(q.dtype),
            _from_bhsd(dk, b, h).astype(k.dtype),
            _from_bhsd(dv, b, h).astype(v.dtype))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q, k, v, causal: bool = True, axis: str = "seq",
                   use_flash: Optional[bool] = None):
    """Sequence-parallel causal attention. Call inside ``shard_map``
    with the sequence dimension sharded over ``axis``.

    q, k, v: [B, S_local, H, D] — this device's sequence shard.
    Returns [B, S_local, H, D] in q.dtype.

    ``use_flash`` (default: auto — on TPU with block-divisible local
    sequences) runs each per-shard block through the pallas flash
    kernel and merges softmax statistics across ring steps; gradients
    flow through a second ring over the pallas backward kernels
    against the globally-merged lse.
    """
    s_local = q.shape[1]
    # Same measured tile ladder as flash_attention's defaults: big
    # tiles run the kernels ~4x faster than the old fixed 128
    # (see parallel/flash_attention.py block ladders); shard lengths
    # that divide no ladder entry degrade to the old behavior.
    from horovod_tpu.parallel.flash_attention import (
        _BLOCK_Q_LADDER, _auto_block,
    )
    block = _auto_block(s_local, _BLOCK_Q_LADDER, None)
    if use_flash is None:
        use_flash = (s_local % block == 0
                     and jax.default_backend() == "tpu")
    elif use_flash and s_local % block != 0:
        raise ValueError(
            f"use_flash requires local sequence {s_local} divisible by "
            f"block {block}")
    if use_flash:
        return _ring_flash(q, k, v, bool(causal), axis, block)
    return _ring_einsum(q, k, v, causal, axis)


def _cached_sharded_attention(mesh, spec, inner):
    """Shared wrapper for the sequence-parallel attention factories
    (ring + ulysses): one manual-sharding island per causal value
    (bounded cache of two) so the returned attention_fn honors its
    ``causal`` argument instead of baking one mask in."""
    cache = {}

    def _build(causal: bool):
        @partial(jaxshim.shard_map, mesh=mesh, in_specs=(spec,) * 3,
                 out_specs=spec)
        def _sharded(q, k, v):
            return inner(q, k, v, causal)
        return _sharded

    def attention_fn(q, k, v, causal=True):
        causal = bool(causal)
        if causal not in cache:
            cache[causal] = _build(causal)
        return cache[causal](q, k, v)

    return attention_fn


def make_ring_attention(mesh, data_axis: str = "data",
                        seq_axis: str = "seq",
                        model_axis: Optional[str] = "model"):
    """Build an ``attention_fn`` for TransformerConfig that runs ring
    attention as a manual-sharding island inside an otherwise
    GSPMD-partitioned jit: batch over ``data_axis``, sequence over
    ``seq_axis``, heads over ``model_axis``. Batch and head dimensions
    need no communication; only the kv rotation over ``seq_axis``
    touches the network."""
    from jax.sharding import PartitionSpec as P

    return _cached_sharded_attention(
        mesh, P(data_axis, seq_axis, model_axis, None),
        lambda q, k, v, causal: ring_attention(q, k, v, causal=causal,
                                               axis=seq_axis))
