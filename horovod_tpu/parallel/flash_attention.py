"""Pallas flash attention — the TPU kernel for the attention hot op.

No reference analog (the reference has no model compute at all); this
is the pallas-native realization of blockwise attention so the
flagship Transformer keeps the MXU busy instead of materializing
O(S²) logits in HBM.

Kernel shape (the canonical TPU flash structure):
- 3D grid (batch*heads, q blocks, kv blocks); the kv-block dimension
  is innermost, so each program sees one [BLOCK_Q, D] query tile and
  one [BLOCK_K, D] kv tile in VMEM — kv streams through, nothing
  holds the whole sequence on-chip;
- running max / normalizer / accumulator live in fp32 VMEM scratch,
  initialized at kv step 0 and flushed to HBM at the last kv step;
- softmax statistics are emitted as [BH, S, 1] arrays with
  (1, BLOCK_Q, 1) blocks — both trailing block dims equal the array
  dims, which satisfies the mosaic tiling rule without replicating
  stats across 128 lanes;
- causal block-skip: kv tiles entirely in the future are predicated
  off with `pl.when`, saving ~half the FLOPs of causal attention;
- `offsets` is a runtime int32[2] (scalar-prefetch, SMEM): the global
  positions of q[0] and k[0]. Ring attention passes traced offsets for
  its rotated kv blocks — no retrace per ring step.

Backward is a pair of pallas kernels (the FlashAttention-2 split):
- dq kernel, grid (BH, q blocks, kv blocks): recomputes each p-block
  from (q, k, lse), forms ds = p * (dp - delta) and accumulates
  dq += ds @ k in fp32 scratch;
- dk/dv kernel, grid (BH, kv blocks, q blocks): same recompute per
  tile, accumulates dv += pᵀ @ do and dk += dsᵀ @ q.
delta = rowsum(do · o) is precomputed once outside (one fused XLA
pass, [BH, S, 1]); lse = m + log l comes from the forward's stats, so
no O(S²) buffer exists anywhere in the backward.

``flash_attention``: differentiable via the kernels above.
``flash_attention_stats``: forward-only variant also returning the
(m, l) softmax statistics, which ring attention merges across shards
(horovod_tpu/parallel/ring_attention.py).
``flash_attention_bwd``: the raw backward entry ring attention calls
per rotated kv shard with the globally-merged lse.

Falls back to interpreter mode off-TPU (tests run it on CPU with tiny
shapes) and to the dense implementation when shapes don't meet block
constraints.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            m_scr, l_scr, acc_scr, *, block_q: int, block_k: int,
            num_k: int, causal: bool, scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = offs_ref[0] + qi * block_q
    k_start = offs_ref[1] + j * block_k
    # Causal block-skip: the whole kv tile is in the future of the
    # whole q tile -> nothing to do.
    visible = jnp.logical_or(
        jnp.logical_not(causal),
        k_start <= q_start + block_q - 1)

    @pl.when(visible)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            allowed = q_pos >= k_pos
            s = jnp.where(allowed, s, _NEG_INF)
        m_prev = m_scr[:]
        block_max = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(allowed, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == num_k - 1)
    def _():
        l = l_scr[:]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        # Stats leave as [1, BQ] rows: the HBM stats tensors are
        # [BH, 1, S] so the TPU (8,128) tiling pads the size-1 dim
        # 8x instead of padding a trailing size-1 lane dim 128x.
        m_ref[0] = jnp.transpose(m_scr[:])
        l_ref[0] = jnp.transpose(l)

@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def _flash_bhsd(q, k, v, offsets, causal: bool, block_q: int,
                block_k: int, interpret: bool):
    """q: [BH, Sq, D]; k, v: [BH, Sk, D]; offsets: int32[2].
    Returns (o [BH,Sq,D], m [BH,1,Sq], l [BH,1,Sq])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    num_k = seq_k // block_k

    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, num_k=num_k,
        causal=causal, scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, seq_q // block_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, offs: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, offs: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, offs: (b, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda b, i, j, offs: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j, offs: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j, offs: (b, 0, i)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ),
        interpret=interpret,
        name="flash_fwd",
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * seq_q * seq_k * d // (2 if causal else 1),
            bytes_accessed=(2 * q.size + k.size + v.size)
            * q.dtype.itemsize,
            transcendentals=bh * seq_q * seq_k,
        ),
    )(offsets, q, k, v)


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    q_start, k_start, block_q: int, block_k: int,
                    causal: bool, scale: float):
    """Shared backward-tile recompute: p = exp(s - lse) and
    ds = p · (dp − delta) · scale for one [BQ, BK] tile. The dq and
    dk/dv kernels differ only in what they contract these with."""
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = jnp.transpose(lse_ref[0])                   # [1,BQ] -> [BQ,1]
    delta = jnp.transpose(delta_ref[0])               # [1,BQ] -> [BQ,1]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [BQ, BK]
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    # Dead rows (l == 0) store lse = +inf -> p underflows to 0.
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [BQ, BK]
    ds = p * (dp - delta) * scale
    return q, k, do, p, ds


def _bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr, *, block_q: int,
                   block_k: int, num_k: int, causal: bool, scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = offs_ref[0] + qi * block_q
    k_start = offs_ref[1] + j * block_k
    visible = jnp.logical_or(
        jnp.logical_not(causal),
        k_start <= q_start + block_q - 1)

    @pl.when(visible)
    def _():
        _, k, _, _, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start, k_start, block_q, block_k, causal, scale)
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == num_k - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    block_q: int, block_k: int, num_q: int,
                    causal: bool, scale: float):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)      # kv block (outer)
    qi = pl.program_id(2)     # q block (inner, streams)

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = offs_ref[0] + qi * block_q
    k_start = offs_ref[1] + j * block_k
    visible = jnp.logical_or(
        jnp.logical_not(causal),
        k_start <= q_start + block_q - 1)

    @pl.when(visible)
    def _():
        q, _, do, p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start, k_start, block_q, block_k, causal, scale)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [BK, D]
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [BK, D]

    @pl.when(qi == num_q - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def _flash_bwd_bhsd(q, k, v, do, lse, delta, offsets, causal: bool,
                    block_q: int, block_k: int, interpret: bool):
    """Backward kernels. q, do: [BH,Sq,D]; k, v: [BH,Sk,D];
    lse, delta: [BH,1,Sq] fp32. Returns (dq, dk, dv) in input dtypes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    num_q = seq_q // block_q
    num_k = seq_k // block_k

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j, offs: (b, i, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j, offs: (b, j, 0))
    stat_spec = pl.BlockSpec((1, 1, block_q),
                             lambda b, i, j, offs: (b, 0, i))

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_q=block_q, block_k=block_k,
            num_k=num_k, causal=causal, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, num_q, num_k),
            in_specs=[q_spec, k_spec, k_spec, q_spec, stat_spec,
                      stat_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
        cost_estimate=pl.CostEstimate(
            flops=6 * bh * seq_q * seq_k * d // (2 if causal else 1),
            bytes_accessed=(2 * q.size + k.size + v.size)
            * q.dtype.itemsize,
            transcendentals=bh * seq_q * seq_k,
        ),
    )(offsets, q, k, v, do, lse, delta)

    # dk/dv: swap grid so the kv block is outer and q streams.
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i, offs: (b, i, 0))
    k_spec2 = pl.BlockSpec((1, block_k, d), lambda b, j, i, offs: (b, j, 0))
    stat_spec2 = pl.BlockSpec((1, 1, block_q),
                              lambda b, j, i, offs: (b, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=block_q, block_k=block_k,
            num_q=num_q, causal=causal, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, num_k, num_q),
            in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, stat_spec2,
                      stat_spec2],
            out_specs=(k_spec2, k_spec2),
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
        ),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        interpret=interpret,
        name="flash_bwd_dkv",
        cost_estimate=pl.CostEstimate(
            flops=10 * bh * seq_q * seq_k * d // (2 if causal else 1),
            bytes_accessed=(q.size + 2 * (k.size + v.size))
            * q.dtype.itemsize,
            transcendentals=bh * seq_q * seq_k,
        ),
    )(offsets, q, k, v, do, lse, delta)
    return dq, dk, dv


def _dense_reference(q, k, v, causal: bool, q_offset, k_offset):
    """Mathematically identical dense formulation (fp32 softmax) — the
    shape fallback and the test oracle for the kernels."""
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(d))
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        allowed = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(allowed[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if causal:
        probs = jnp.where(allowed[None, None], probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


def _shapes_ok(seq_q, seq_k, block_q, block_k):
    return seq_q % block_q == 0 and seq_k % block_k == 0


# Default block ladders. Measured on v5e silicon (B=4..8, S=2048,
# D=64..128): 512x1024 tiles run the fwd+bwd kernels 3.8-4.2x faster
# than 128x128 — small tiles pay per-program fixed costs and shallow
# MXU passes far exceeding their VMEM savings. ``None`` block args
# auto-pick the largest ladder entry dividing the sequence, so odd
# lengths (ring shards, tests) degrade gracefully instead of falling
# back to dense.
#
# The kernels' resident VMEM grows linearly with D: per program
# roughly (block_q + 2*block_k) * D tile elements plus the
# (block_q, D) f32 accumulator (the backward adds do/dq tiles of the
# same shape). _ladders_for halves the ladder per doubling past
# _HEAD_DIM_BASE so the working set stays roughly D-invariant; tiles
# never drop below the 128-lane MXU width.
#
# Measured at D=256 on v5e silicon (PR 27: B4 H20 S4096, one layer's
# forward + dq + dk/dv, ms): 512x1024 8.08 + 20.46 = 28.54; 512x512
# 28.98; 1024x512 27.36; 256x1024 32.82; the halved pair 256x512,
# which ADVICE r05 reasoned and nobody had run, 11.46 + 24.78 = 36.24
# (27% slower); 256x256 46.29; 128x512 53.79. 1024x1024 and 2048x512
# do not fit VMEM. So the default pair holds through D=256 and the
# halving starts past it (D=512 and beyond are still unmeasured).
_BLOCK_Q_LADDER = (512, 256, 128)
_BLOCK_K_LADDER = (1024, 512, 256, 128)
_HEAD_DIM_BASE = 256  # the largest D the default ladder was measured at


def _ladders_for(head_dim: int):
    """(q_ladder, k_ladder) scaled to ``head_dim``: the measured
    512x1024 defaults up to D=256, then each doubling of D halves the
    leading tiles (floor 128) so per-program VMEM stays level."""
    q_top, k_top = _BLOCK_Q_LADDER[0], _BLOCK_K_LADDER[0]
    d = max(1, int(head_dim))
    while d > _HEAD_DIM_BASE and (q_top > 128 or k_top > 128):
        q_top = max(128, q_top // 2)
        k_top = max(128, k_top // 2)
        d //= 2
    q_ladder = tuple(b for b in _BLOCK_Q_LADDER if b <= q_top)
    k_ladder = tuple(b for b in _BLOCK_K_LADDER if b <= k_top)
    return q_ladder, k_ladder


def _auto_block(seq: int, ladder, explicit) -> int:
    if explicit is not None:
        return min(explicit, seq)
    for b in ladder:
        if seq % b == 0:
            return b
    return min(ladder[-1], seq)


def _to_bhsd(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bhsd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _run(q, k, v, offsets, causal, block_q, block_k, interpret):
    b, seq_q, h, d = q.shape
    o, m, l = _flash_bhsd(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v), offsets,
                          causal, block_q, block_k, bool(interpret))
    o = _from_bhsd(o, b, h)
    m = m[:, 0].reshape(b, h, seq_q)
    l = l[:, 0].reshape(b, h, seq_q)
    return o, m, l


def flash_attention_stats(q, k, v, causal: bool = True,
                          q_offset=0, k_offset=0,
                          block_q: Optional[int] = None,
                          block_k: Optional[int] = None,
                          interpret: Optional[bool] = None
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Forward-only flash attention that also returns the softmax
    statistics: (o [B,Sq,H,D], m [B,H,Sq] running max, l [B,H,Sq]
    normalizer). Ring attention merges these across rotated kv shards.
    Offsets may be traced values (one compilation serves every ring
    step)."""
    seq_q, seq_k = q.shape[1], k.shape[1]
    q_ladder, k_ladder = _ladders_for(q.shape[-1])
    block_q = _auto_block(seq_q, q_ladder, block_q)
    block_k = _auto_block(seq_k, k_ladder, block_k)
    if not _shapes_ok(seq_q, seq_k, block_q, block_k):
        raise ValueError(
            f"sequence lengths ({seq_q}, {seq_k}) must be divisible by "
            f"blocks ({block_q}, {block_k})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32)])
    return _run(q, k, v, offsets, causal, block_q, block_k, interpret)


def _lse_from_stats(m, l):
    """[B,H,S] stats -> [BH,1,S] fp32 lse; +inf marks dead rows so the
    backward's exp(s - lse) underflows to exactly 0 for them. The
    size-1 middle dim keeps S on the 128-lane axis — a trailing size-1
    dim would tile-pad the tensor 128x in HBM."""
    b, h, s = m.shape
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.where(l > 0.0, l, 1.0)),
                    jnp.inf)
    return lse.reshape(b * h, 1, s)


def flash_attention_bwd(q, k, v, o, m, l, do, causal: bool = True,
                        q_offset=0, k_offset=0,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """Raw flash backward against externally-merged softmax stats.

    q, k, v, o, do: [B,S,H,D]; m, l: [B,H,Sq] (as returned — or ring-
    merged — from flash_attention_stats). Returns (dq, dk, dv) in the
    input dtypes. Ring attention calls this once per rotated kv shard
    with the *global* lse, which makes per-shard contributions sum to
    the exact full-sequence gradient."""
    b, seq_q, h, d = q.shape
    seq_k = k.shape[1]
    q_ladder, k_ladder = _ladders_for(d)
    block_q = _auto_block(seq_q, q_ladder, block_q)
    block_k = _auto_block(seq_k, k_ladder, block_k)
    if not _shapes_ok(seq_q, seq_k, block_q, block_k):
        raise ValueError(
            f"sequence lengths ({seq_q}, {seq_k}) must be divisible by "
            f"blocks ({block_q}, {block_k})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32)])
    qb, kb, vb, dob, ob = (_to_bhsd(x) for x in (q, k, v, do, o))
    lse = _lse_from_stats(m, l)
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)[:, None, :]   # [BH,1,S], see _lse_from_stats
    dq, dk, dv = _flash_bwd_bhsd(qb, kb, vb, dob, lse, delta, offsets,
                                 bool(causal), block_q, block_k,
                                 bool(interpret))
    return (_from_bhsd(dq, b, h), _from_bhsd(dk, b, h),
            _from_bhsd(dv, b, h))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, offsets, causal, block_q, block_k, interpret):
    return _run(q, k, v, offsets, causal, block_q, block_k, interpret)[0]


def _flash_fwd(q, k, v, offsets, causal, block_q, block_k, interpret):
    o, m, l = _run(q, k, v, offsets, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, m, l, offsets)


def _flash_bwd(causal, block_q, block_k, interpret, residuals, g):
    import numpy as np
    q, k, v, o, m, l, offsets = residuals
    dq, dk, dv = flash_attention_bwd(
        q, k, v, o, m, l, g, causal=causal,
        q_offset=offsets[0], k_offset=offsets[1],
        block_q=block_q, block_k=block_k, interpret=interpret)
    d_offsets = np.zeros(offsets.shape, jax.dtypes.float0)
    return dq, dk, dv, d_offsets


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    q_offset=0, k_offset=0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Blockwise-softmax attention. q, k, v: [B, S, H, D] (the module
    layout of models/transformer.py); returns [B, Sq, H, D] in q.dtype.

    ``q_offset``/``k_offset`` (python ints or traced scalars) are the
    global positions of element 0, shifting the causal mask — ring
    attention's rotated kv blocks use this.

    Precision: the in-kernel dots follow jax's matmul-precision config,
    like every other TPU matmul — bf16 multiplies with f32 accumulation
    by default (measured ~1e-2 vs a float64 reference at S=512, i.e.
    BETTER than a dense attention at the same default). Wrap the call
    in ``jax.default_matmul_precision("float32")`` for ~2e-6 agreement
    at several times the MXU cost; the context reaches inside the
    pallas kernel (verified on v5e silicon)."""
    seq_q, seq_k = q.shape[1], k.shape[1]
    q_ladder, k_ladder = _ladders_for(q.shape[-1])
    bq = _auto_block(seq_q, q_ladder, block_q)
    bk = _auto_block(seq_k, k_ladder, block_k)
    if not _shapes_ok(seq_q, seq_k, bq, bk):
        if not causal:
            raise ValueError("non-causal path requires block-divisible "
                             "sequence lengths")
        if jax.default_backend() == "tpu":
            # On the chip this puts the O(S^2) logits in HBM; say so
            # (the warnings module shows it once per distinct shape).
            warnings.warn(
                f"flash_attention: sequence lengths ({seq_q}, {seq_k}) "
                f"of q{q.shape} k{k.shape} divide no tile (tried "
                f"{bq}x{bk}); running the dense fallback, which "
                f"materializes the [B,H,Sq,Sk] f32 logits in HBM",
                RuntimeWarning, stacklevel=2)
        return _dense_reference(q, k, v, causal, q_offset, k_offset)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32)])
    return _flash(q, k, v, offsets, bool(causal), bq, bk,
                  bool(interpret))
