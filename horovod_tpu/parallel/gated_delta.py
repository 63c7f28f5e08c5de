"""Pallas gated delta rule: the recurrence of a Gated DeltaNet layer
(arXiv:2412.06464) in the delta rule's chunked form
(arXiv:2406.06484), as TPU kernels, forward and backward.

A value head carries a float32 state ``S`` [Dk, Dv], zero at the start
of a sequence (``g <= 0`` the log of the decay, ``beta`` in (0, 1), or
in (0, 2) where the state's transition ``I - beta k k^T`` may have a
negative eigenvalue)::

    S   <- exp(g_t) S
    u_t  = beta_t (v_t - S^T k_t)
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

The state is a matrix and its update a rank-one *correction*, so there
is no elementwise form: ``gated_delta_rule_reference`` is the literal
``lax.scan`` (one tiny step a position), the tests' oracle and no path
to ship. The kernels compute a chunk of ``C`` positions at a time. With
``G_i`` the running sum of ``g`` inside the chunk and ``S`` the state
that enters it::

    A_ij = -beta_i (k_i . k_j) exp(G_i - G_j)   for j < i
    T    = (I - A)^-1
    W    = T (beta exp(G) K)        U = T (beta V)
    V'   = U - W S
    O    = (Q exp(G)) S + tril(Q K^T exp(G_i - G_j)) V'
    S'   = exp(G_C) S + (K exp(G_C - G))^T V'

Kernel shape:

- grid ``(batch, key head, chunk)``, the chunk innermost and in order;
  the state of the key head's value heads lives in float32 VMEM scratch
  between the chunks of a sequence. **One step serves every value head
  that reads the key head** (two at the model's shape): ``K K^T`` and
  ``Q K^T`` are computed once, and ``dq`` and ``dk`` leave the backward
  kernel already summed over them. q, k, v and o stay ``[B, S, H x D]``
  in HBM: a block is a chunk's rows of one head's columns, so nothing is
  transposed or repeated around the kernels. ``G`` and ``beta`` come as
  ``[B, Hv, chunks, C]``, a head's whole table resident, a chunk one
  row of it.
- **the triangular inverse is all MXU**: ``A`` is strictly lower
  triangular, so ``A^C = 0`` and ``(I - A)^-1 = (I + A)(I + A^2)(I +
  A^4)...`` exactly, ``log2(C) - 1`` squarings and as many products, in
  float32 at full precision (``_unit_lower_inverse``). **Exact is not
  stable**: where the keys of a chunk resemble one another and the
  decay is slow the powers grow like binomial coefficients and the
  inverse is what is left of their cancellation; with ``beta`` under 1
  and the keys a seeded model has the doublings hold (the cell
  ``qwen3next-injit-1chip`` runs them), with ``beta`` up to 2 they do
  not (a chunk of 128, keys of 96 at a mean cosine of 0.5, ``beta`` in
  (1, 2), no decay: an error of 1e21 of the largest entry in float32
  on the CPU, not finite at 0.8), so a call that says ``beta_max``
  above 1 takes the inverse by forward substitution over blocks of
  ``_SOLVE_BLOCK`` rows (``_unit_lower_inverse_by_blocks``), chosen
  statically: the program of a call that does not is what it was.
- **a head is whole lane tiles in the kernels**: a block is a head's
  columns, so a head of 96 or 192 channels is laid out behind zeros up
  to 128 or 256 (``lay_heads``; a zero key channel leaves every product
  as it is and its row of the state at zero, a zero value channel is a
  column of the state and of ``o`` that stays zero). The rule lays out
  what it is handed, or takes operands that a layer laid out before
  its prologue (``filled`` then says what is no padding). The gauge
  ``hvd_gdn_layout`` has both widths.
- ``gdn_fwd`` also writes the state that **entered** each chunk
  (``[B, Hv, chunks, Dk, Dv]`` float32: 64 KB a head and chunk, 537 MB
  a layer at the model's shape and a chunk of 64, 268 MB at 128) and
  **the chunk's inverse** ``T`` (``[B, Hv, chunks, C, C]`` float32, as
  computed: 4 C bytes a position and head whatever the chunk, 268 MB a
  layer at the model's shape); a recomputed block keeps both, as it
  keeps every kernel's output. ``gdn_bwd`` walks the chunks from the
  last to the first with ``dS`` in scratch: it makes a chunk's ``W``,
  ``U`` and ``V'`` again from the entering state and the forward's
  ``T``, **takes no inverse** (it was most of the kernel: 72 of its 104
  MXU passes at a chunk of 128, a float32 product six), and propagates
  through every product above, ``T`` included (``dA = T^T dT T^T``, in
  float32 at full precision, which is why ``T`` travels as float32).
  The gauge ``hvd_gdn_chunks`` has what a chunk hands over.
- MXU operands take q's type (bfloat16 in the model: exact for q, k and
  v, a rounding for ``T``, ``S`` and the gated operands), accumulation
  is float32; the state, ``G``, ``beta``, every exponential and the
  inverse are float32 whatever the operands' type.

``gated_delta_rule`` is differentiable through the two kernels
(``custom_vjp``) in q, k, v, g and beta. Off the TPU the kernels run in
interpreter mode, as the flash and scan kernels do.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# Chunk length by sequence length, as (longest sequence, chunk) rungs;
# the last rung stands past it. Measured on v5e silicon (PR 33; the
# model's shape: B1, 16 key heads serving 32 value heads, S16384,
# 128/128, bfloat16 q, k, v, one layer; the forward through
# ``gated_delta_rule`` and forward + backward through its gradient, by
# the host's clock, ms; the inverse by ``_unit_lower_inverse``):
#   chunk   fwd      fwd+bwd   entering states   products / recurrence
#    32     18.837   45.319    1,074 MB          1.3
#    64     13.571   31.525      537 MB          2.1
#   128     11.316   25.179      268 MB          5.4
#   256     33.473   72.180      134 MB          19.1
#   128     11.231   17.191      268 + 268 MB (T)  5.4   (PR 48)
# (128 and 64 again in a second call: 11.301 and 25.165, 13.556 and
# 31.514.) A longer chunk executes more (``chunk_flops`` over the
# recurrence's 7 Dk Dv a position: the last column) and is faster all
# the same up to 128: there every product fills the MXU's 128 x 128
# and there are half the grid steps and entering states of 64; at 256
# the inverse's fourteen products of 256^3 a head and chunk are the
# kernel. In the cell's step (traced) the kernels read 9.77 ms forward
# and 12.97 backward a layer at 128, 12.2 and 17.1 at 64.
# **PR 48's line: the backward takes the forward's ``T`` and no
# inverse.** The same call on one machine, its parent beside it, each
# twice: 11.247 and 25.045, 11.246 and 25.053 before; 11.237 and 17.186
# the second time: the backward alone 13.80 -> 5.96 ms, the forward the
# same with 268 MB more to write; the output and all five gradients
# the parent's to the bit. In the cell's step the kernels read 9.72 ms
# forward and 5.13 backward a layer since.
# The inverse, same shape and clock (PR 33, a later call; the doublings
# twice, first and last): forward substitution by row blocks as ``(I -
# A) = (I - D)(I - M)``, ``D`` the diagonal blocks (inverted by
# doublings, every block in one product of full width), ``M = (I -
# D)^-1 (A - D)``, then ``X_i = (I - D)^-1_i + M_i X_<i`` a block of
# rows at a time, against ``_unit_lower_inverse``'s doublings:
#   chunk 128      full + thin products   fwd      fwd+bwd
#   doublings      12                     11.319   25.214  (11.326, 25.186)
#   blocks of 16    7 + 7                 11.930   26.308
#   blocks of 32    9 + 3                 11.271   24.978
#   blocks of 64   11 + 1                 11.534   25.516
#   chunk 64: doublings 13.552 and 31.491 (13.553, 31.540); blocks of
#   16 14.685 and 33.324; of 32 14.237 and 32.777
# Blocks of 16 save five products of 128^3 and lose more than that to
# seven thin ones in a chain; blocks of 32 are 0.9% ahead at 128 and
# 4% behind at 64. The doublings stay: five lines, no block size to
# choose, within a hundredth of the best form at the ladder's chunk.
# The forms agree to one bfloat16 step in o and the gradients (``T``
# is rounded to the operands' type for ``W`` and ``U``).
_CHUNK_LADDER = ((None, 128),)

# The lanes of a vector register: a block of a head's columns is whole
# multiples of it, so a head of another width is laid out behind zeros.
LANES = 128

# Rows a block of the inverse's forward substitution where ``beta``
# passes 1 (``_unit_lower_inverse_by_blocks``). Measured at key heads
# of 96 over value heads of 192 (PR 46; ``olmohybrid-injit-1chip``'s
# shape: B1, S8192, 30 key heads each serving one value head, bfloat16
# q, k, v, ``beta`` in (0.1, 1.9), one layer; v5e silicon, the host's
# clock, ms; the error is of the inverse alone against float64, float32
# on the CPU, a chunk of 128, keys of 96 at the mean cosine given,
# ``beta`` in (1, 2), no decay):
#   layout, chunk, inverse             fwd      fwd+bwd  error at cosine 0.5 / 0.8 / 0.95
#   the rule lays out, 128, blocks 8    8.883   18.714   1e-6 / 5e-6 / 1e-5
#   the rule lays out, 128, blocks 16   7.740   16.432   7e-5 / 3e-3 / 2e-2
#   the rule lays out, 128, doublings   7.082   15.236   1e21 / nan / nan
#   laid out already, 128, blocks 8     9.035   18.614
#   the rule lays out, 64, blocks 8    10.641   22.909
#   the rule lays out, 128, blocks 8    8.897   13.332   (PR 48: the
#     backward takes the forward's ``T``; its parent in the same call
#     8.907 and 18.726, each again 8.895 and 13.339, 8.892 and 18.711;
#     entering states 126 MB a layer and ``T`` 126 MB)
# (``lay_heads`` has what laying out costs.) Either way the
# kernels hold a head as 128 and 256 lanes and run 1.78 times the
# recurrence's products (``hvd_gdn_layout``; the benchmark's
# ``gdn_layout_fill`` reads 56.25%): the MXU's tiles are 128 wide, so a
# block whose last dimension is a whole head of 96 (``[B, H, S, D]``
# operands: the compiler takes them, PR 46, compiled for a described
# v5e and not run) would execute the same products behind a transpose
# each way. Blocks of 8 cost 12% of the kernels (1.1% of the cell's
# step) over blocks of 16 and are three orders closer where the keys
# resemble one another; on seeded weights both read the same (the
# check's gaps). The doublings are the fastest and are lost.
_SOLVE_BLOCK = 8


def _chunk_for(seq: int) -> int:
    """The ladder's chunk for a sequence of ``seq``, halved while the
    half still holds the whole sequence (and 8 positions)."""
    chunk = next(c for longest, c in _CHUNK_LADDER
                 if longest is None or seq <= longest)
    while chunk // 2 >= max(seq, 8):
        chunk //= 2
    return chunk


def kept_bytes(chunk: int, dk: int, dv: int) -> int:
    """The bytes a value head's chunk hands from the forward kernel to
    the backward: the entering state and ``T``, float32 (the Kimi rule's
    too: ``parallel.kda``)."""
    return 4 * (dk * dv + chunk * chunk)


def _note_chunks(seq: int, chunk: int, dk: int, dv: int) -> None:
    """``hvd_gdn_chunks{kind=...}`` of the call being traced
    (docs/metrics.md); ``dk`` and ``dv`` a head's widths as the kernels
    hold them."""
    from horovod_tpu.common import basics
    basics.note_traced(
        "hvd_gdn_chunks",
        "the gated delta rule traced last: chunks a sequence, positions "
        "a chunk, and the bytes a value head's chunk hands from the "
        "forward kernel to the backward",
        {"chunks": -(-seq // chunk), "chunk_length": chunk,
         "kept_bytes_per_chunk": kept_bytes(chunk, dk, dv)})


def _note_layout(dk: int, dv: int, laid_dk: int, laid_dv: int) -> None:
    """``hvd_gdn_layout{kind=...}`` of the call being traced
    (docs/metrics.md)."""
    from horovod_tpu.common import basics
    basics.note_traced(
        "hvd_gdn_layout",
        "the gated delta rule traced last: a key and a value head's "
        "width as handed over and as the kernels hold them",
        {"key_dim": dk, "value_dim": dv, "laid_key_dim": laid_dk,
         "laid_value_dim": laid_dv})


# -- inside a chunk ---------------------------------------------------------

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # x y^T
_NN = (((1,), (0,)), ((), ()))      # x y
_TN = (((0,), (0,)), ((), ()))      # x^T y


def _mm(x, y, dims, dtype):
    """A product on the MXU: operands in ``dtype``, float32 out."""
    return jax.lax.dot_general(x.astype(dtype), y.astype(dtype), dims,
                               preferred_element_type=_F32)


def _mm_f32(x, y, dims=_NN):
    return jax.lax.dot_general(x, y, dims, preferred_element_type=_F32,
                               precision=jax.lax.Precision.HIGHEST)


def _unit_lower_inverse(a, order: Optional[int] = None):
    """``(I - a)^-1`` of a strictly lower triangular ``a`` [C, C]:
    ``(I + a)(I + a^2)(I + a^4)...``, exact because ``a^C = 0``
    (``order``: a smaller power of two at which ``a`` vanishes already,
    as a block-diagonal ``a`` of such blocks does)."""
    size = a.shape[0] if order is None else order
    rows = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    t = jnp.where(rows == cols, 1.0, 0.0).astype(_F32) + a
    power, n = a, 2
    while n < size:
        power = _mm_f32(power, power)           # a^n
        t = t + _mm_f32(power, t)               # (I + a^n) t
        n *= 2
    return t


def _unit_lower_inverse_by_blocks(a, block: int):
    """``(I - a)^-1`` of a strictly lower triangular ``a`` [C, C] by
    forward substitution over blocks of ``block`` rows: with ``D`` the
    diagonal blocks of ``a``, ``(I - a) = (I - D)(I - M)``, ``M = (I -
    D)^-1 (a - D)``; ``(I - D)^-1`` by doublings (every block in one
    product of full width: ``_unit_lower_inverse``), then
    ``Y_i = E_i + M_i Y_<i`` a block of rows at a time and ``Y (I -
    D)^-1``.

    **Doublings over the whole chunk are not stable here.** The series
    ``(I + a)(I + a^2)(I + a^4)...`` of an ``a`` whose entries share a
    sign forms powers that grow like binomial coefficients (``a^32`` of
    a 128 x 128 chunk reaches 1e14 and more where the keys of a chunk
    resemble one another and the decay is slow) and an inverse of
    order 1 out of their cancellation. Under one decay a head and
    ``beta`` under 1 the decay cuts every power off on a seeded model's
    keys (with ``beta`` up to 2 it does not: the module's docstring);
    with a decay a channel the slow channels keep
    ``M_ij`` near ``k_i . k_j`` across the chunk. On v5e silicon (PR
    41) the second layer's rule at a chunk of 128 came out at 2e37
    from position 8,832 on and not finite behind it, at 64 and 32
    sound. Forward substitution is stable whatever ``a`` holds; inside
    a block of 16 the powers stay below 1e4."""
    size = a.shape[0]
    if size <= block:
        return _unit_lower_inverse(a)
    rows = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    shift = block.bit_length() - 1
    diagonal = jnp.where((rows >> shift) == (cols >> shift), a, 0.0)
    d_inv = _unit_lower_inverse(diagonal, order=block)
    m = _mm_f32(d_inv, a - diagonal)
    eye = jnp.where(rows == cols, 1.0, 0.0).astype(_F32)
    done = [eye[:block]]
    for at in range(block, size, block):
        below = jnp.zeros((size - at, size), _F32)
        done.append(eye[at:at + block] + _mm_f32(
            m[at:at + block], jnp.concatenate(done + [below], axis=0)))
    return _mm_f32(jnp.concatenate(done, axis=0), d_inv)


def _masks(chunk: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return rows == cols, rows > cols, rows >= cols


def _col(row, eye):
    """[C, 1] of a [1, C] row."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """[1, C] of a [C, 1] column."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _last(row):
    """[1, 1]: the last entry of a [1, C] row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _chunk(q, k, v, g_row, b_row, state, kk, qk, mm, solve=None, t=None):
    """A value head's chunk from the state that entered it: everything
    the forward writes and the backward propagates through. q, k
    [C, Dk]; v [C, Dv]; g_row, b_row [1, C] float32 (``G`` and
    ``beta``); state [Dk, Dv] float32; kk, qk [C, C] the key head's raw
    products; ``solve`` the rows a block of the inverse's forward
    substitution, None for doublings over the whole chunk; ``t`` the
    chunk's inverse where the caller has it (the backward kernel, from
    the forward): no inverse is taken then."""
    eye, lower, lower_eq = _masks(q.shape[0])
    g_col, b_col = _col(g_row, eye), _col(b_row, eye)
    decay = jnp.where(lower_eq, jnp.exp(jnp.minimum(g_col - g_row, 0.0)),
                      0.0)                              # exp(G_i - G_j)
    if t is None:
        a = jnp.where(lower, -b_col * kk * decay, 0.0)
        t = _unit_lower_inverse(a) if solve is None \
            else _unit_lower_inverse_by_blocks(a, solve)
    e_col = jnp.exp(g_col)
    kb = (b_col * e_col) * k.astype(_F32)
    vb = b_col * v.astype(_F32)
    w = _mm(t, kb, _NN, mm)
    u = _mm(t, vb, _NN, mm)
    v_new = u - _mm(w, state, _NN, mm)
    p = qk * decay
    g_last = _last(g_row)
    e_last = jnp.exp(g_last - g_col)                    # exp(G_C - G_i)
    return dict(eye=eye, lower=lower, b_col=b_col, decay=decay, t=t,
                e_col=e_col, kb=kb, vb=vb, w=w, v_new=v_new, p=p,
                g_last=g_last, e_last=e_last)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, sent_ref, t_ref,
                s_scr, *, rep: int, dv: int, solve):
    from jax.experimental import pallas as pl
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    q, k = q_ref[0], k_ref[0]
    mm = q.dtype
    kk = _mm(k, k, _NT, mm)
    qk = _mm(q, k, _NT, mm)
    for r in range(rep):
        v = v_ref[0, :, r * dv:(r + 1) * dv]
        state = s_scr[r]
        sent_ref[0, r, 0] = state
        x = _chunk(q, k, v, g_ref[0, r, pl.ds(c, 1), :],
                   b_ref[0, r, pl.ds(c, 1), :], state, kk, qk, mm, solve)
        t_ref[0, r, 0] = x["t"]
        out = _mm(q.astype(_F32) * x["e_col"], state, _NN, mm) \
            + _mm(x["p"], x["v_new"], _NN, mm)
        o_ref[0, :, r * dv:(r + 1) * dv] = out.astype(o_ref.dtype)
        s_scr[r] = jnp.exp(x["g_last"]) * state + _mm(
            k.astype(_F32) * x["e_last"], x["v_new"], _TN, mm)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, sent_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr,
                *, rep: int, dv: int, n_chunks: int):
    from jax.experimental import pallas as pl
    step = pl.program_id(2)
    c = n_chunks - 1 - step

    @pl.when(step == 0)                   # the sequence's last chunk
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    q, k = q_ref[0], k_ref[0]
    mm = q.dtype
    qf, kf = q.astype(_F32), k.astype(_F32)
    kk = _mm(k, k, _NT, mm)
    qk = _mm(q, k, _NT, mm)
    dq = jnp.zeros(q.shape, _F32)
    dk = jnp.zeros(k.shape, _F32)
    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, q.shape[0]), 1)
    for r in range(rep):
        v = v_ref[0, :, r * dv:(r + 1) * dv]
        vf = v.astype(_F32)
        state = sent_ref[0, r, 0]
        d_out = do_ref[0, :, r * dv:(r + 1) * dv]
        d_state = ds_scr[r]
        x = _chunk(q, k, v, g_ref[0, r, pl.ds(c, 1), :],
                   b_ref[0, r, pl.ds(c, 1), :], state, kk, qk, mm,
                   t=t_ref[0, r, 0])
        eye, t, decay = x["eye"], x["t"], x["decay"]
        b_col, e_col, e_last = x["b_col"], x["e_col"], x["e_last"]
        kd = kf * e_last
        # O = (Q e) S + P V';  S' = e_C S + Kd^T V'
        d_vnew = _mm(x["p"], d_out, _TN, mm) + _mm(kd, d_state, _NN, mm)
        d_p = _mm(d_out, x["v_new"], _NT, mm)
        d_qe = _mm(d_out, state, _NT, mm)
        d_kd = _mm(x["v_new"], d_state, _NT, mm)
        decay_last = jnp.exp(x["g_last"])
        # V' = U - W S
        ds_scr[r] = _mm(qf * e_col, d_out, _TN, mm) \
            + decay_last * d_state - _mm(x["w"], d_vnew, _TN, mm)
        d_w = -_mm(d_vnew, state, _NT, mm)
        # W = T kb, U = T vb
        d_t = _mm(d_w, x["kb"], _NT, mm) + _mm(d_vnew, x["vb"], _NT, mm)
        d_kb = _mm(t, d_w, _TN, mm)
        d_vb = _mm(t, d_vnew, _TN, mm)
        # T = (I - A)^-1: dA = T^T dT T^T, below the diagonal
        d_a = jnp.where(x["lower"],
                        _mm_f32(_mm_f32(t, d_t, _TN), t, _NT), 0.0)
        # A = -beta kk decay;  P = qk decay
        d_qk = d_p * decay
        d_kk = -b_col * d_a * decay
        d_decay = (d_p * qk - b_col * d_a * kk) * decay
        tail = rowsum(d_kd * kf) * e_last       # d / d(G_C - G_i)
        d_g_col = rowsum(d_qe * qf) * e_col - tail \
            + rowsum(d_kb * kf) * b_col * e_col + rowsum(d_decay)
        d_g_last = jnp.sum(tail, axis=0, keepdims=True) \
            + decay_last * jnp.sum(rowsum(state * d_state), axis=0,
                                   keepdims=True)
        d_b_col = rowsum(d_vb * vf) + rowsum(d_kb * kf) * e_col \
            - rowsum(d_a * kk * decay)
        d_g_row = _row(d_g_col, eye) \
            - jnp.sum(d_decay, axis=0, keepdims=True) \
            + jnp.where(lane == q.shape[0] - 1, d_g_last, 0.0)
        dg_ref[0, r, pl.ds(c, 1), :] = d_g_row
        db_ref[0, r, pl.ds(c, 1), :] = _row(d_b_col, eye)
        dv_ref[0, :, r * dv:(r + 1) * dv] = (b_col * d_vb).astype(
            dv_ref.dtype)
        dq = dq + d_qe * e_col + _mm(d_qk, k, _NN, mm)
        dk = dk + d_kd * e_last + _mm(d_qk, q, _TN, mm) \
            + (b_col * e_col) * d_kb \
            + _mm(d_kk, k, _NN, mm) + _mm(d_kk, k, _TN, mm)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)


# -- the calls ---------------------------------------------------------------

def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 << 20)


def _specs(chunk, dk, dv, rep, n_chunks, reverse: bool):
    """Block specs over the grid (batch, key head, chunk); with
    ``reverse`` the chunks are walked from the last to the first."""
    from jax.experimental import pallas as pl
    at = (lambda c: n_chunks - 1 - c) if reverse else (lambda c: c)
    return dict(
        qk=pl.BlockSpec((1, chunk, dk), lambda b, h, c: (b, at(c), h)),
        v=pl.BlockSpec((1, chunk, rep * dv), lambda b, h, c: (b, at(c), h)),
        gates=pl.BlockSpec((1, rep, n_chunks, chunk),
                           lambda b, h, c: (b, h, 0, 0)),
        sent=pl.BlockSpec((1, rep, 1, dk, dv),
                          lambda b, h, c: (b, h, at(c), 0, 0)),
        t=pl.BlockSpec((1, rep, 1, chunk, chunk),
                       lambda b, h, c: (b, h, at(c), 0, 0)))


def chunk_flops(chunk: int, dk: int, dv: int, rep: int) -> int:
    """Multiply-adds x 2 of the forward kernel's products for one key
    head's chunk (``rep`` value heads)."""
    doublings = max(0, chunk.bit_length() - 2)
    shared = 2 * 2 * chunk * chunk * dk
    inverse = 2 * doublings * 2 * chunk ** 3
    head = inverse + 2 * chunk * chunk * (dk + dv) \
        + 2 * 2 * chunk * dk * dv + 2 * chunk * chunk * dv \
        + 2 * chunk * dk * dv
    return shared + rep * head


def _bwd_chunk_flops(chunk: int, dk: int, dv: int, rep: int) -> int:
    """Multiply-adds x 2 of the backward kernel's products for one key
    head's chunk: ``W``, ``U`` and ``V'`` again, every product's two
    cotangents and ``dA``'s two of the chunk's width; no inverse."""
    shared = 2 * 2 * chunk * chunk * dk
    head = 2 * (7 * chunk * chunk * dk + 5 * chunk * chunk * dv
                + 7 * chunk * dk * dv + 2 * chunk ** 3)
    return shared + rep * head


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "solve",
                                             "interpret"))
def _gdn_fwd(q, k, v, g, beta, chunk: int, heads, solve, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    hk, hv = heads
    rep = hv // hk
    bt, padded = q.shape[:2]
    dk, dv = q.shape[2] // hk, v.shape[2] // hv
    n_chunks = padded // chunk
    s = _specs(chunk, dk, dv, rep, n_chunks, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rep=rep, dv=dv, solve=solve),
        grid=(bt, hk, n_chunks),
        in_specs=[s["qk"], s["qk"], s["v"], s["gates"], s["gates"]],
        out_specs=(s["v"], s["sent"], s["t"]),
        out_shape=(
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((bt, hv, n_chunks, dk, dv), _F32),
            jax.ShapeDtypeStruct((bt, hv, n_chunks, chunk, chunk), _F32)),
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="gdn_fwd",
        cost_estimate=pl.CostEstimate(
            flops=bt * hk * n_chunks * chunk_flops(chunk, dk, dv, rep),
            transcendentals=bt * hv * n_chunks * chunk * (chunk + 2),
            bytes_accessed=(q.size + k.size) * q.dtype.itemsize
            + 2 * v.size * v.dtype.itemsize + 4 * (g.size + beta.size)
            + bt * hv * n_chunks * kept_bytes(chunk, dk, dv)),
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("chunk", "heads", "interpret"))
def _gdn_bwd(q, k, v, g, beta, sent, t, d_out, chunk: int, heads,
             interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    hk, hv = heads
    rep = hv // hk
    bt, padded = q.shape[:2]
    dk, dv = q.shape[2] // hk, v.shape[2] // hv
    n_chunks = padded // chunk
    s = _specs(chunk, dk, dv, rep, n_chunks, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, rep=rep, dv=dv, n_chunks=n_chunks),
        grid=(bt, hk, n_chunks),
        in_specs=[s["qk"], s["qk"], s["v"], s["gates"], s["gates"],
                  s["sent"], s["t"], s["v"]],
        out_specs=(s["qk"], s["qk"], s["v"], s["gates"], s["gates"]),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, _F32),
            jax.ShapeDtypeStruct(beta.shape, _F32)),
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="gdn_bwd",
        cost_estimate=pl.CostEstimate(
            flops=bt * hk * n_chunks * _bwd_chunk_flops(chunk, dk, dv, rep),
            transcendentals=bt * hv * n_chunks * chunk * (chunk + 2),
            bytes_accessed=2 * (q.size + k.size) * q.dtype.itemsize
            + 3 * v.size * v.dtype.itemsize + 8 * (g.size + beta.size)
            + 4 * (sent.size + t.size)),
    )(q, k, v, g, beta, sent, t, d_out)


def _laid(dim: int, lane: int = LANES) -> int:
    """A head's width as the kernels hold it: the next multiple of
    ``lane``."""
    return -(-dim // lane) * lane


def laid_columns(n: int, dim: int, lane: int = LANES) -> int:
    """The width of ``n`` columns laid out in runs of ``dim``
    (``lay_heads``)."""
    return n // dim * _laid(dim, lane)


def lay_heads(x, dim: int, lane: int = LANES):
    """``x`` [..., n x ``dim``] with every run of ``dim`` columns behind
    zeros up to the next multiple of ``lane``, [..., n x laid]; ``x``
    itself where ``dim`` is whole lanes. How heads off the lane tile
    travel between the delta rules' kernels (the prologue, the rule,
    the epilogue): laid out once behind the projection and taken back
    once before the output projection. A pad, which XLA runs as a
    relayout at a quarter of the memory's pace (``qkvz``'s [8192,
    17280] bfloat16 to 23,040 columns: 3.05 ms on v5e, 0.81 read and
    written once; the slice back 2.92, ``y``'s [8192, 7680] to 5,760
    0.70); **a product with a 0/1 matrix a block of four runs reads the
    same** (3.04, 3.06 and 0.84: PR 46 wrote it, measured it and took
    it out), so what is left is a kernel that reads the runs where they
    lie (PERF.md section 7)."""
    laid = _laid(dim, lane)
    if laid == dim:
        return x
    heads = x.reshape(*x.shape[:-1], -1, dim)
    heads = jnp.pad(heads, ((0, 0),) * (heads.ndim - 1) + ((0, laid - dim),))
    return heads.reshape(*x.shape[:-1], -1)


def take_heads(x, dim: int, lane: int = LANES):
    """``lay_heads``'s way back: [..., n x ``dim``] of [..., n x laid]."""
    laid = _laid(dim, lane)
    if laid == dim:
        return x
    return x.reshape(*x.shape[:-1], -1, laid)[..., :dim].reshape(
        *x.shape[:-1], -1)


def _laid_out(q, k, v, g, beta, chunk, lane):
    """The kernels' operands from the module's: time padded to whole
    chunks (a padded step has beta 0 and g 0: the state passes through
    it unchanged), a head's channels behind zeros up to a multiple of
    ``lane`` (a zero key channel leaves every product as it is and its
    row of the state at zero; a zero value channel is a column of the
    state and of ``o`` that stays zero), heads folded into the columns,
    ``G`` (the running sum of ``g`` inside each chunk) and ``beta`` as
    [B, Hv, chunks, C] float32."""
    bt, seq, hv = v.shape[:3]
    n_chunks = -(-seq // chunk)

    def gate(x):
        x = jnp.pad(x.astype(_F32), ((0, 0), (0, n_chunks * chunk - seq),
                                     (0, 0)))
        return x.transpose(0, 2, 1).reshape(bt, hv, n_chunks, chunk)

    return (*(_laid_heads(x, chunk, lane) for x in (q, k, v)),
            jnp.cumsum(gate(g), axis=-1), gate(beta))


def _laid_heads(x, chunk, lane):
    """[B, S', H x D'] of the module's [B, S, H, D]: whole chunks of
    steps, whole multiples of ``lane`` channels a head, zeros behind
    both."""
    bt, seq = x.shape[:2]
    x = lay_heads(x.reshape(bt, seq, -1), x.shape[3], lane)
    return jnp.pad(x, ((0, 0), (0, -seq % chunk), (0, 0)))


def _taken_back(x, like, lane):
    """The module's [B, S, H, D] of a kernel's [B, S', H x D']: the
    padded steps and the zero channels cut off."""
    return take_heads(x[:, :like.shape[1]], like.shape[3], lane).reshape(
        like.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rule(q, k, v, g, beta, chunk, solve, lane, interpret):
    return _rule_fwd(q, k, v, g, beta, chunk, solve, lane, interpret)[0]


def _rule_fwd(q, k, v, g, beta, chunk, solve, lane, interpret):
    heads = (q.shape[2], v.shape[2])
    out, sent, t = _gdn_fwd(*_laid_out(q, k, v, g, beta, chunk, lane),
                            chunk=chunk, heads=heads, solve=solve,
                            interpret=interpret)
    return _taken_back(out, v, lane), (q, k, v, g, beta, sent, t)


def _rule_bwd(chunk, solve, lane, interpret, res, d_out):
    q, k, v, g, beta, sent, t = res
    bt, seq, hv = v.shape[:3]
    ops = _laid_out(q, k, v, g, beta, chunk, lane)
    padded = ops[0].shape[1]
    d_out = _laid_heads(d_out.astype(v.dtype), chunk, lane)
    dq, dk, dv, d_gsum, d_beta = _gdn_bwd(
        *ops, sent, t, d_out, chunk=chunk, heads=(q.shape[2], hv),
        interpret=interpret)
    # G is the running sum of g inside a chunk: g_j reaches every G_i
    # with i >= j
    d_g = jnp.flip(jnp.cumsum(jnp.flip(d_gsum, -1), axis=-1), -1)
    gate = lambda x, like: x.reshape(bt, hv, padded).transpose(0, 2, 1)[
        :, :seq].astype(like.dtype)
    return (*(_taken_back(d, like, lane)
              for d, like in ((dq, q), (dk, k), (dv, v))),
            gate(d_g, g), gate(d_beta, beta))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: Optional[int] = None,
                     interpret: Optional[bool] = None,
                     beta_max: float = 1.0, lane: Optional[int] = None,
                     filled: Optional[tuple] = None):
    """``o`` [B, S, Hv, Dv] in ``v.dtype`` of the recurrence in the
    module docstring. q, k: [B, S, Hk, Dk] (already normalised and
    scaled: the rule takes them as they come); v: [B, S, Hv, Dv] with
    ``Hv`` a multiple of ``Hk`` (value head ``h`` reads key head ``h //
    (Hv / Hk)``); g, beta: [B, S, Hv], ``g <= 0``, ``beta`` in (0,
    ``beta_max``). ``chunk`` None takes the ladder's
    (``_CHUNK_LADDER``), a power of two; a length that is no multiple of
    it is padded with steps that leave the state as it is. ``beta_max``
    above 1 (a state transition with negative eigenvalues) takes the
    chunk's inverse by blocks of ``_SOLVE_BLOCK`` rows, 1 and under by
    doublings. ``lane`` None lays a head out to a multiple of
    ``LANES`` where the kernels are compiled and as it comes under the
    interpreter, which takes any block; operands that come laid out
    already (``lay_heads``) say in ``filled`` how many of a key and of a
    value head's channels are no padding, for the gauge
    ``hvd_gdn_layout``. Differentiable in all five operands."""
    if k.shape != q.shape or v.shape[:2] != q.shape[:2] \
            or v.shape[2] % q.shape[2] or g.shape != v.shape[:3] \
            or beta.shape != g.shape:
        raise ValueError(
            f"q{q.shape} k{k.shape} v{v.shape} g{g.shape} beta{beta.shape}"
            f": want [B,S,Hk,Dk] twice, [B,S,Hv,Dv], [B,S,Hv] twice")
    chunk = _chunk_for(q.shape[1]) if chunk is None else int(chunk)
    if chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} is no power of two")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lane = (1 if interpret else LANES) if lane is None else int(lane)
    solve = min(chunk, _SOLVE_BLOCK) if beta_max > 1.0 else None
    laid = _laid(q.shape[3], lane), _laid(v.shape[3], lane)
    _note_chunks(q.shape[1], chunk, *laid)
    _note_layout(*(filled or (q.shape[3], v.shape[3])), *laid)
    return _rule(q, k, v, g, beta, chunk, solve, lane, bool(interpret))


def gated_delta_rule_reference(q, k, v, g, beta):
    """The literal recurrence, one ``lax.scan`` step a position, in
    float32: the kernels' oracle."""
    rep = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(x.astype(_F32), rep, axis=2) for x in (q, k))
    v, g, beta = (x.astype(_F32) for x in (v, g, beta))

    def step(state, xs):
        qt, kt, vt, gt, bt = xs         # [B,H,Dk] x2, [B,H,Dv], [B,H] x2
        state = jnp.exp(gt)[..., None, None] * state
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt))
        state = state + kt[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    s0 = jnp.zeros((q.shape[0], v.shape[2], q.shape[3], v.shape[3]), _F32)
    timed = lambda x: jnp.moveaxis(x, 1, 0)
    _, out = jax.lax.scan(step, s0, tuple(
        timed(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)
