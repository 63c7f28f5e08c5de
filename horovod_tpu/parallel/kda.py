"""Pallas Kimi delta attention: the gated delta rule with **a decay for
every key channel** (KDA, arXiv:2510.26692), chunked, as TPU kernels,
forward and backward. The scalar rule's sibling
(``parallel.gated_delta``, whose doublings, product helpers and masks
it shares).

A head carries a float32 state ``S`` [Dk, Dv], zero at the start of a
sequence (``g <= 0`` the log of the decay, **a vector of Dk a
position**; ``beta`` in (0, 1))::

    S   <- diag(exp(g_t)) S
    u_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

``kda_reference`` is the literal ``lax.scan``, the tests' oracle. The
kernels compute a chunk of ``C`` positions at a time. With ``G_i`` the
running sum of ``g`` inside the chunk ([C, Dk]) and ``S`` the state
that enters it::

    M_ij = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])      (j < i)
    P_ij = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])      (j <= i)
    T    = (I + diag(beta) M)^-1
    W    = T (beta K exp(G))        U = T (beta V)
    V'   = U - W S
    O    = (Q exp(G)) S + P V'
    S'   = diag(exp(G_C)) S + (K exp(G_C - G))^T V'

**The decay sits inside the contraction over channels**, so ``M`` and
``P`` are no product of q, k and a [C, C] table as under one decay a
head: ``exp(G_i - G_j)`` has to be split as ``exp(G_i - G_r) exp(G_r -
G_j)`` around a reference position ``r``, and a factor whose exponent
is positive grows with the distance to ``r``. The chunk's lower
triangle is therefore tiled by **levels** (``_levels``):

- the diagonal blocks of ``sub`` positions, each around its own first
  position: the row factor ``exp(G_i - G_r)`` is a decay, the column
  factor ``exp(G_r - G_j)`` grows, by at most ``exp(-(sub - 1) min g)``.
  With the published bound ``g >= -5`` and ``sub`` 16 that is exp(75),
  inside float32's exp(88); at 32 it is not. **That is what the bound
  on g is for**, and why ``sub`` is at most 16 for such a gate. The
  growing factor stays float32 and its products run at full precision;
  its exponent is capped at ``_GROW_CAP`` so that a g beyond the bound
  gives a wrong number and not a NaN.
- below the diagonal blocks, a binary tree: blocks of ``sub`` pair up,
  the pairs pair up, ... up to the chunk. Inside a group the upper
  half's rows meet the lower half's columns around the upper half's
  first position, where **both factors are decays** (at most 1): one
  table ``exp(-|G - G_r|)`` serves rows and columns alike, and the
  operands take q's type.

**The triangular inverse** ``T`` is forward substitution over blocks
of 16 rows, the scalar rule's doublings inside a block
(``_unit_lower_inverse_by_blocks``, which says why doublings over the
whole chunk do not do here).

Every level is one product of the chunk's full width, masked to the
level's blocks (the MXU multiplies 128 columns whether a block has 16
or 128). The backward propagates through the same levels: ``dG`` a
channel is ``k dk`` (``q dq``) of the row side less that of the column
side, and what reaches the reference position cancels.

Kernel shape: grid ``(batch, head, chunk)``, the chunk innermost and in
order; the state lives **transposed** ([Dv, Dk]: the decay of a chunk
scales its columns, a row vector) in float32 VMEM scratch between the
chunks of a sequence. q, k, v, g and o stay ``[B, S, H x D]`` in HBM:
a block is a chunk's rows of one head's columns. ``g`` comes as it is
(float32) and the kernels take its running sum themselves, a product
with a triangle of ones at full precision, as they give ``dg`` back
from ``dG``. ``beta`` comes as ``[B, H, chunks, C]``. ``kda_fwd`` also
writes the state that **entered** each chunk (``[B, H, chunks, Dv,
Dk]`` float32, 64 KB a head and chunk at 128/128) and **the chunk's
inverse** ``T`` (``[B, H, chunks, C, C]`` float32, as computed: 64 KB
at a chunk of 128); ``kda_bwd`` walks the chunks from the last to the
first with ``dS`` in scratch, makes a chunk's running sums, levels,
``W``, ``U`` and ``V'`` again from the entering state and the
forward's ``T``, **takes no inverse**, and propagates through every
product above (``dA = T^T dT T^T`` in float32 at full precision, which
is why ``T`` travels as float32). The gauge ``hvd_kda_chunks`` has what
a chunk hands over.

MXU operands take q's type where they are bounded by 1 (bfloat16 in
the model), accumulation is float32; the state, ``G``, ``beta``, every
exponential, the diagonal level and the inverse are float32.

``kimi_delta_attention`` is differentiable through the two kernels
(``custom_vjp``) in q, k, v, g and beta. Off the TPU the kernels run in
interpreter mode, as the flash, scan and scalar-rule kernels do.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from horovod_tpu.parallel.gated_delta import (
    _F32, _NN, _NT, _TN, _col, _compiler_params, _masks, _mm, _mm_f32, _row,
    _unit_lower_inverse_by_blocks, kept_bytes,
)

# Chunk and sub-block length by sequence length, as (longest sequence,
# chunk, sub-block) rungs; the last rung stands past it. Measured on
# v5e silicon (PR 41; the model's shape: B1, 32 heads, S16384, 128/128,
# bfloat16 q, k, v, float32 g in [-5, 0], one layer; the forward
# through ``kimi_delta_attention`` and forward + backward through its
# gradient, by the host's clock, ms):
#   chunk  sub   fwd      fwd+bwd   entering states   levels
#    32    16    32.331   73.222    1,074 MB          2
#    64    16    22.134   50.345      537 MB          3
#    64     8    22.653   52.023      537 MB          4
#    64    32    22.399   50.969      537 MB          2
#   128    16    18.537   43.889      268 MB          4
#   128     8    18.238   43.924      268 MB          5
#   128    32    17.484   41.619      268 MB          3
#   256    16    31.665   77.329      134 MB          5
#   128    16    17.652   32.255      268 + 268 MB (T)  4   (PR 48)
# (the scalar rule, ``gated_delta``, at 32 key heads over 32 value heads
# and its chunk of 128, same call: 12.956 and 29.073. With the inverse
# by doublings over the whole chunk, an earlier call: 128/16 16.821 and
# 40.623, 64/16 20.115 and 46.573, 256/16 40.531 and 95.025: forward
# substitution costs 8% at 128 and is a fifth faster at 256; it is
# there because doublings are not stable,
# ``_unit_lower_inverse_by_blocks``.) As under one decay a head, 128 is
# the chunk: every product fills the MXU's 128 x 128 and there are half
# the grid steps and entering states of 64. A level more or less moves
# the kernel by 0.1 to 5%: each is three products of the chunk's width.
# **A sub-block of 32 is 5% faster and is not taken**: its growing
# factor reaches exp(155) under the published bound of -5, past
# float32. 8 and 16 read alike; 16 is the largest the bound allows. In
# the cell's step (traced) the kernels read 15.1 ms forward and 22.4
# backward a layer. **PR 48's line: the backward takes the forward's
# ``T`` and no inverse.** The same call on one machine, its parent
# beside it, each twice: 17.647 and 42.074, 17.642 and 42.054 before
# (other seeded operands than PR 41's); 17.651 and 32.259 the second
# time: the backward alone 24.43 -> 14.60 ms, the forward the same
# with 268 MB more to write; the output and all five gradients the
# parent's to the bit. In the cell's step the kernels read 15.1 ms
# forward and 12.6 backward a layer since.
_CHUNK_LADDER = ((None, 128, 16),)

# Rows a block of the triangular inverse's forward substitution.
_SOLVE_BLOCK = 16

# The cap on the diagonal level's growing exponent: exp(80) is finite
# in float32 and above what a gate bounded at -5 reaches over 16
# positions (75).
_GROW_CAP = 80.0


def _lengths_for(seq: int):
    """The ladder's ``(chunk, sub)`` for a sequence of ``seq``, the
    chunk halved while the half still holds the whole sequence (and 8
    positions)."""
    chunk, sub = next((c, s) for longest, c, s in _CHUNK_LADDER
                      if longest is None or seq <= longest)
    while chunk // 2 >= max(seq, 8):
        chunk //= 2
    return chunk, min(sub, chunk)


def _note_chunks(seq: int, chunk: int, sub: int, dk: int, dv: int) -> None:
    """``hvd_kda_chunks{kind=...}`` of the call being traced
    (docs/metrics.md)."""
    from horovod_tpu.common import basics
    basics.note_traced(
        "hvd_kda_chunks",
        "the Kimi delta attention rule traced last: chunks a sequence, "
        "positions a chunk, positions a diagonal sub-block, and the "
        "bytes a head's chunk hands from the forward kernel to the "
        "backward",
        {"chunks": -(-seq // chunk), "chunk_length": chunk,
         "sub_block_length": sub,
         "kept_bytes_per_chunk": kept_bytes(chunk, dk, dv)})


# -- inside a chunk ---------------------------------------------------------

def _ref_rows(g, group: int, at: int):
    """[C, D]: every row replaced by row ``at`` of its group of
    ``group`` rows."""
    parts = [jnp.broadcast_to(g[s + at:s + at + 1], (group, g.shape[1]))
             for s in range(0, g.shape[0], group)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _levels(g_sum, sub: int):
    """The tiling of a chunk's lower triangle, as ``(row factor, column
    factor, mask, exact)`` a level: ``exp(G_i - G_j)`` inside ``mask``
    is the row factor at ``i`` times the column factor at ``j``.
    ``exact`` marks the diagonal blocks, whose column factor grows."""
    size = g_sum.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
    shift = sub.bit_length() - 1
    ref = _ref_rows(g_sum, sub, 0)
    levels = [(jnp.exp(g_sum - ref),
               jnp.exp(jnp.minimum(ref - g_sum, _GROW_CAP)),
               (rows >> shift) == (cols >> shift), True)]
    group = 2 * sub
    while group <= size:
        half, shift = group // 2, shift + 1
        decay = jnp.exp(-jnp.abs(g_sum - _ref_rows(g_sum, group, half)))
        mask = ((rows >> shift) == (cols >> shift)) \
            & ((rows & (group - 1)) >= half) & ((cols & (group - 1)) < half)
        levels.append((decay, decay, mask, False))
        group *= 2
    return levels


def _level_mm(x, y, dims, exact: bool, mm):
    return _mm_f32(x, y, dims) if exact else _mm(x, y, dims, mm)


def _chunk(q, k, v, g, b_row, state_t, sub: int, mm, t=None):
    """A head's chunk from the state that entered it: everything the
    forward writes and the backward propagates through. q, k [C, Dk];
    v [C, Dv]; g [C, Dk] float32 (a position's own log-decay); b_row
    [1, C] float32; state_t [Dv, Dk] float32; ``t`` the chunk's inverse
    where the caller has it (the backward kernel, from the forward): no
    inverse is taken then."""
    size = q.shape[0]
    eye, lower, lower_eq = _masks(size)
    ones = jnp.where(lower_eq, 1.0, 0.0).astype(_F32)
    g_sum = _mm_f32(ones, g)                            # G, [C, Dk]
    b_col = _col(b_row, eye)
    qf, kf, vf = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    levels = _levels(g_sum, sub)
    m = jnp.zeros((size, size), _F32)
    p = jnp.zeros((size, size), _F32)
    for row_f, col_f, mask, exact in levels:
        both = _level_mm(jnp.concatenate([qf * row_f, kf * row_f], axis=0),
                         kf * col_f, _NT, exact, mm)
        p = p + jnp.where(mask & lower_eq, both[:size], 0.0)
        m = m + jnp.where(mask & lower, both[size:], 0.0)
    if t is None:
        t = _unit_lower_inverse_by_blocks(-b_col * m, _SOLVE_BLOCK)
    gam = jnp.exp(g_sum)
    kb = (b_col * gam) * kf
    vb = b_col * vf
    w = _mm(t, kb, _NN, mm)
    u = _mm(t, vb, _NN, mm)
    v_new = u - _mm(w, state_t, _NT, mm)
    g_last = g_sum[size - 1:size]                       # [1, Dk]
    e_last = jnp.exp(g_last - g_sum)                    # exp(G_C - G_i)
    return dict(eye=eye, lower=lower, lower_eq=lower_eq, ones=ones,
                b_col=b_col, qf=qf, kf=kf, vf=vf, levels=levels, m=m, p=p,
                t=t, gam=gam, kb=kb, vb=vb, w=w, v_new=v_new, g_last=g_last,
                e_last=e_last)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, sent_ref, t_ref,
                s_scr, *, sub: int):
    from jax.experimental import pallas as pl
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    q = q_ref[0]
    mm = q.dtype
    state_t = s_scr[...]
    sent_ref[0, 0, 0] = state_t
    x = _chunk(q, k_ref[0], v_ref[0], g_ref[0], b_ref[0, 0, pl.ds(c, 1), :],
               state_t, sub, mm)
    t_ref[0, 0, 0] = x["t"]
    out = _mm(x["qf"] * x["gam"], state_t, _NT, mm) \
        + _mm(x["p"], x["v_new"], _NN, mm)
    o_ref[0] = out.astype(o_ref.dtype)
    s_scr[...] = jnp.exp(x["g_last"]) * state_t + _mm(
        x["v_new"], x["kf"] * x["e_last"], _TN, mm)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, sent_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr,
                *, sub: int, n_chunks: int):
    from jax.experimental import pallas as pl
    step = pl.program_id(2)
    c = n_chunks - 1 - step

    @pl.when(step == 0)                   # the sequence's last chunk
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    q = q_ref[0]
    mm = q.dtype
    size = q.shape[0]
    state_t = sent_ref[0, 0, 0]
    d_out = do_ref[0]
    d_state_t = ds_scr[...]
    x = _chunk(q, k_ref[0], v_ref[0], g_ref[0], b_ref[0, 0, pl.ds(c, 1), :],
               state_t, sub, mm, t=t_ref[0, 0, 0])
    qf, kf, vf = x["qf"], x["kf"], x["vf"]
    t, b_col, gam, e_last = x["t"], x["b_col"], x["gam"], x["e_last"]
    rowsum = lambda a: jnp.sum(a, axis=1, keepdims=True)
    colsum = lambda a: jnp.sum(a, axis=0, keepdims=True)
    kd = kf * e_last
    # O = (Q exp G) S + P V';  S' = diag(exp G_C) S + Kd^T V'
    d_vnew = _mm(x["p"], d_out, _TN, mm) + _mm(kd, d_state_t, _NT, mm)
    d_p = jnp.where(x["lower_eq"], _mm(d_out, x["v_new"], _NT, mm), 0.0)
    d_qg = _mm(d_out, state_t, _NN, mm)
    d_kd = _mm(x["v_new"], d_state_t, _NN, mm)
    gam_last = jnp.exp(x["g_last"])
    # V' = U - W S
    ds_scr[...] = _mm(d_out, qf * gam, _TN, mm) + gam_last * d_state_t \
        - _mm(d_vnew, x["w"], _TN, mm)
    d_w = -_mm(d_vnew, state_t, _NN, mm)
    # W = T kb, U = T vb
    d_t = _mm(d_w, x["kb"], _NT, mm) + _mm(d_vnew, x["vb"], _NT, mm)
    d_kb = _mm(t, d_w, _TN, mm)
    d_vb = _mm(t, d_vnew, _TN, mm)
    # T = (I - A)^-1: dA = T^T dT T^T, below the diagonal;  A = -beta M
    d_a = jnp.where(x["lower"], _mm_f32(_mm_f32(t, d_t, _TN), t, _NT), 0.0)
    d_m = -b_col * d_a
    d_b_col = rowsum(d_vb * vf) + rowsum(d_kb * kf * gam) \
        - rowsum(d_a * x["m"])
    dq = d_qg * gam
    dk = d_kd * e_last + (b_col * gam) * d_kb
    d_gsum = d_qg * qf * gam - d_kd * kd + d_kb * x["kb"]
    d_g_last = colsum(d_kd * kd) + gam_last * colsum(state_t * d_state_t)
    # M and P, level by level: the row side's factor is exp(G - G_r),
    # the column side's exp(G_r - G)
    for row_f, col_f, mask, exact in x["levels"]:
        rows = jnp.concatenate([qf * row_f, kf * row_f], axis=0)
        k_col = kf * col_f
        d_both = jnp.concatenate(
            [jnp.where(mask, d_p, 0.0), jnp.where(mask, d_m, 0.0)], axis=0)
        d_rows = _level_mm(d_both, k_col, _NN, exact, mm)
        d_col = _level_mm(d_both, rows, _TN, exact, mm)
        dq = dq + d_rows[:size] * row_f
        dk = dk + d_rows[size:] * row_f + d_col * col_f
        d_gsum = d_gsum + d_rows[:size] * rows[:size] \
            + d_rows[size:] * rows[size:] - d_col * k_col
    at_last = jax.lax.broadcasted_iota(jnp.int32, d_gsum.shape, 0) == size - 1
    d_gsum = d_gsum + jnp.where(at_last, d_g_last, 0.0)
    # G is the running sum of g: g_j reaches every G_i with i >= j
    dg_ref[0] = _mm_f32(x["ones"], d_gsum, _TN)
    db_ref[0, 0, pl.ds(c, 1), :] = _row(d_b_col, x["eye"])
    dv_ref[0] = (b_col * d_vb).astype(dv_ref.dtype)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)


# -- the calls ---------------------------------------------------------------

def _specs(chunk, dk, dv, n_chunks, reverse: bool):
    """Block specs over the grid (batch, head, chunk); with ``reverse``
    the chunks are walked from the last to the first."""
    from jax.experimental import pallas as pl
    at = (lambda c: n_chunks - 1 - c) if reverse else (lambda c: c)
    return dict(
        qk=pl.BlockSpec((1, chunk, dk), lambda b, h, c: (b, at(c), h)),
        v=pl.BlockSpec((1, chunk, dv), lambda b, h, c: (b, at(c), h)),
        beta=pl.BlockSpec((1, 1, n_chunks, chunk),
                          lambda b, h, c: (b, h, 0, 0)),
        sent=pl.BlockSpec((1, 1, 1, dv, dk),
                          lambda b, h, c: (b, h, at(c), 0, 0)),
        t=pl.BlockSpec((1, 1, 1, chunk, chunk),
                       lambda b, h, c: (b, h, at(c), 0, 0)))


def chunk_flops(chunk: int, sub: int, dk: int, dv: int) -> int:
    """Multiply-adds x 2 of the forward kernel's products for one head's
    chunk."""
    block = min(chunk, _SOLVE_BLOCK)
    doublings = max(0, block.bit_length() - 2)
    levels = (chunk // sub).bit_length()
    inverse = 2 * doublings * 2 * chunk ** 3    # the diagonal blocks'
    if chunk > block:   # two products of full width, a thin one a block
        inverse += 2 * 2 * chunk ** 3 + sum(
            2 * block * at * chunk for at in range(block, chunk, block))
    return 2 * chunk * chunk * dk \
        + levels * 2 * 2 * chunk * chunk * dk + inverse \
        + 2 * chunk * chunk * (dk + dv) \
        + 2 * 2 * chunk * dk * dv + 2 * chunk * chunk * dv \
        + 2 * chunk * dk * dv


def _bwd_chunk_flops(chunk: int, sub: int, dk: int, dv: int) -> int:
    """Multiply-adds x 2 of the backward kernel's products for one head's
    chunk: the running sum, the levels, ``W``, ``U`` and ``V'`` again,
    every product's two cotangents (a level's are twice its own
    product) and ``dA``'s two of the chunk's width; no inverse."""
    levels = (chunk // sub).bit_length()
    return 2 * ((5 + 6 * levels) * chunk * chunk * dk
                + 5 * chunk * chunk * dv + 7 * chunk * dk * dv
                + 2 * chunk ** 3)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "sub", "heads", "interpret"))
def _kda_fwd(q, k, v, g, beta, chunk: int, sub: int, heads: int,
             interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, padded = q.shape[:2]
    dk, dv = q.shape[2] // heads, v.shape[2] // heads
    n_chunks = padded // chunk
    s = _specs(chunk, dk, dv, n_chunks, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sub=sub),
        grid=(bt, heads, n_chunks),
        in_specs=[s["qk"], s["qk"], s["v"], s["qk"], s["beta"]],
        out_specs=(s["v"], s["sent"], s["t"]),
        out_shape=(
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((bt, heads, n_chunks, dv, dk), _F32),
            jax.ShapeDtypeStruct((bt, heads, n_chunks, chunk, chunk), _F32)),
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="kda_fwd",
        cost_estimate=pl.CostEstimate(
            flops=bt * heads * n_chunks * chunk_flops(chunk, sub, dk, dv),
            transcendentals=bt * heads * n_chunks * chunk * dk
            * (3 + (chunk // sub).bit_length()),
            bytes_accessed=(q.size + k.size) * q.dtype.itemsize
            + 2 * v.size * v.dtype.itemsize + 4 * (g.size + beta.size)
            + bt * heads * n_chunks * kept_bytes(chunk, dk, dv)),
    )(q, k, v, g, beta)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "sub", "heads", "interpret"))
def _kda_bwd(q, k, v, g, beta, sent, t, d_out, chunk: int, sub: int,
             heads: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, padded = q.shape[:2]
    dk, dv = q.shape[2] // heads, v.shape[2] // heads
    n_chunks = padded // chunk
    s = _specs(chunk, dk, dv, n_chunks, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub, n_chunks=n_chunks),
        grid=(bt, heads, n_chunks),
        in_specs=[s["qk"], s["qk"], s["v"], s["qk"], s["beta"], s["sent"],
                  s["t"], s["v"]],
        out_specs=(s["qk"], s["qk"], s["v"], s["qk"], s["beta"]),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, _F32),
            jax.ShapeDtypeStruct(beta.shape, _F32)),
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="kda_bwd",
        cost_estimate=pl.CostEstimate(
            flops=bt * heads * n_chunks
            * _bwd_chunk_flops(chunk, sub, dk, dv),
            transcendentals=bt * heads * n_chunks * chunk * dk
            * (3 + (chunk // sub).bit_length()),
            bytes_accessed=2 * (q.size + k.size) * q.dtype.itemsize
            + 3 * v.size * v.dtype.itemsize + 8 * (g.size + beta.size)
            + 4 * (sent.size + t.size)),
    )(q, k, v, g, beta, sent, t, d_out)


def _laid_out(q, k, v, g, beta, chunk):
    """The kernels' operands from the module's: time padded to whole
    chunks (a padded step has beta 0 and g 0: the state passes through
    it unchanged), heads folded into the columns, ``g`` float32,
    ``beta`` as [B, H, chunks, C] float32."""
    bt, seq, heads = q.shape[:3]
    n_chunks = -(-seq // chunk)
    pad = n_chunks * chunk - seq

    def timed(x):
        x = x.reshape(bt, seq, -1)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    return (timed(q), timed(k), timed(v), timed(g.astype(_F32)),
            timed(beta.astype(_F32)).transpose(0, 2, 1).reshape(
                bt, heads, n_chunks, chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rule(q, k, v, g, beta, chunk, sub, interpret):
    return _rule_fwd(q, k, v, g, beta, chunk, sub, interpret)[0]


def _rule_fwd(q, k, v, g, beta, chunk, sub, interpret):
    seq = q.shape[1]
    out, sent, t = _kda_fwd(*_laid_out(q, k, v, g, beta, chunk), chunk=chunk,
                            sub=sub, heads=q.shape[2], interpret=interpret)
    return out[:, :seq].reshape(v.shape), (q, k, v, g, beta, sent, t)


def _rule_bwd(chunk, sub, interpret, res, d_out):
    q, k, v, g, beta, sent, t = res
    bt, seq, heads = v.shape[:3]
    ops = _laid_out(q, k, v, g, beta, chunk)
    padded = ops[0].shape[1]
    d_out = jnp.pad(d_out.astype(v.dtype).reshape(bt, seq, -1),
                    ((0, 0), (0, padded - seq), (0, 0)))
    dq, dk, dv, dg, d_beta = _kda_bwd(
        *ops, sent, t, d_out, chunk=chunk, sub=sub, heads=heads,
        interpret=interpret)
    d_beta = d_beta.reshape(bt, heads, padded).transpose(0, 2, 1)
    return (dq[:, :seq].reshape(q.shape), dk[:, :seq].reshape(k.shape),
            dv[:, :seq].reshape(v.shape),
            dg[:, :seq].reshape(g.shape).astype(g.dtype),
            d_beta[:, :seq].astype(beta.dtype))


_rule.defvjp(_rule_fwd, _rule_bwd)


def kimi_delta_attention(q, k, v, g, beta, chunk: Optional[int] = None,
                         sub: Optional[int] = None,
                         interpret: Optional[bool] = None):
    """``o`` [B, S, H, Dv] in ``v.dtype`` of the recurrence in the
    module docstring. q, k: [B, S, H, Dk] (already normalised and
    scaled: the rule takes them as they come); v: [B, S, H, Dv]; g:
    [B, S, H, Dk], ``g <= 0``, **and no less than -75 / (sub - 1)**
    (the diagonal level, module docstring: -5 at the default 16); beta:
    [B, S, H]. ``chunk`` and ``sub`` None take the ladder's
    (``_CHUNK_LADDER``), powers of two, ``sub`` at most ``chunk``; a
    length that is no multiple of the chunk is padded with steps that
    leave the state as it is. Differentiable in all five operands."""
    if k.shape != q.shape or v.shape[:3] != q.shape[:3] \
            or g.shape != q.shape or beta.shape != q.shape[:3]:
        raise ValueError(
            f"q{q.shape} k{k.shape} v{v.shape} g{g.shape} beta{beta.shape}"
            f": want [B,S,H,Dk] twice, [B,S,H,Dv], [B,S,H,Dk], [B,S,H]")
    ladder = _lengths_for(q.shape[1])
    chunk = ladder[0] if chunk is None else int(chunk)
    sub = min(ladder[1], chunk) if sub is None else int(sub)
    if chunk & (chunk - 1) or sub & (sub - 1) or not 0 < sub <= chunk:
        raise ValueError(f"chunk {chunk} and sub-block {sub}: powers of "
                         f"two, the sub-block at most the chunk")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _note_chunks(q.shape[1], chunk, sub, q.shape[3], v.shape[3])
    return _rule(q, k, v, g, beta, chunk, sub, bool(interpret))


def kda_reference(q, k, v, g, beta):
    """The literal recurrence, one ``lax.scan`` step a position, in
    float32: the kernels' oracle."""
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))

    def step(state, xs):
        qt, kt, vt, gt, bt = xs     # [B,H,Dk] x2, [B,H,Dv], [B,H,Dk], [B,H]
        state = jnp.exp(gt)[..., None] * state
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt))
        state = state + kt[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    s0 = jnp.zeros((*q.shape[:1], *q.shape[2:], v.shape[3]), _F32)
    timed = lambda x: jnp.moveaxis(x, 1, 0)
    _, out = jax.lax.scan(step, s0, tuple(
        timed(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)
