"""Pallas prologue of the delta rules: the way from the fused q, k, v
projection to the rule's kernels in **one pass each way**. What Kimi
delta attention (``models.ling3flash``) and the Gated DeltaNet
(``models.qwen3next``) both do between ``x W_qkv`` and
``parallel.kda`` / ``parallel.gated_delta``::

    p_t = sum_j w[j] x_{t - (taps - 1) + j}     a channel, causal
    a   = silu(p)
    y   = a rsqrt(sum_head(a^2) + eps)          q's and k's heads
    q   = y D^-1/2                              q's heads
    out = cast(.)                               to x's type

``x`` comes in the model's type (bfloat16) and q, k, v leave in it, laid
out ``[B, S, H x D]`` as the rule's kernels take them; everything
between is float32 in VMEM on a tile that is read once. The rounding
points are those of the composition written out in XLA
(``CausalDepthwiseConv`` -> ``silu`` -> the L2 norm -> the scale -> the
cast), except that the backward adds the taps' gradients in float32 and
rounds ``dx`` once, where the transposed composition rounds each tap's
to ``x``'s type first.

Kernel shape: grid ``(batch, tile of rows)``, the tiles of a sequence
innermost; a step holds a tile's rows of **every** column (a block of
``x``'s leading ``C`` columns: ``x`` may be wider, as the Gated
DeltaNet's ``qkvz`` is, and no slice is materialised) and walks the
heads, a head's columns at a time (whole lane tiles: 128 in both
models that brought the kernels; heads off the tile come laid out, as
``qkv_prologue`` says): the head's rows go to a float32
staging buffer behind the ``taps - 1`` rows before the tile, the taps
read it at ``taps`` offsets, and SiLU, the norm, the scale and the cast
run on row chunks that stay in registers. A pass of the loop takes a
few heads, each through a staging buffer of its own, so that their
chains of loads and stores overlap (``_ROW_LADDER`` has the sweep).

* forward (``qkv_prologue_fwd``): tiles in order; the last rows of the
  previous tile are carried in float32 scratch, zeros at a sequence's
  start.
* backward (``qkv_prologue_bwd``): takes ``x``, the kernel and (dq, dk,
  dv), recomputes a tile's pre-activation and norm, and writes ``dx`` in
  ``x``'s type and ``dkernel`` [taps, C] float32. ``dx`` of a row needs
  the pre-activation's gradient of the ``taps - 1`` rows **after** it:
  the tiles are walked from the last to the first (as ``kda_bwd`` walks
  its chunks) with the first rows' gradient carried in scratch, and the
  rows of ``x`` before the tile come in by a second block spec. Per
  backward ``x`` is read once, the three gradients once, ``dx`` written
  once. ``dkernel`` is summed in float32 over rows, tiles and batch,
  eight partial rows a tap until the last step.

``qkv_prologue`` is differentiable through the two kernels
(``custom_vjp``) in ``x`` and the kernel. **Its outputs are not worth
keeping across a recomputed block** (403 MB a Kimi delta attention
layer at 16,384 tokens, remade in a millisecond or two):
``KERNEL_PREFIX`` is what a remat policy that keeps kernels' outputs
leaves out (``glm_moe._keep_kernel_outputs``). The kernels' names carry
no prefix of the rules' (``kda_``, ``gdn_``): the benchmark's readers
book kernels by prefix, and this time is not the recurrence's.

Off the TPU the kernels run in interpreter mode, as every kernel here.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

_F32 = jnp.float32

# The kernels' names begin with this (a remat policy tells them by it).
KERNEL_PREFIX = "qkv_prologue"

# Rows of a float32 register tile: what the staging buffers keep before
# (after) a tile's rows for the taps to reach into.
_LEAD = 8
# Rows of the second block of ``x`` the backward takes before a tile:
# a bfloat16 memory tile.
_HALO = 16

# Rows a tile, rows a register-resident chunk and heads a pass of the
# head loop by sequence length, as (longest sequence, rows, chunk,
# heads) rungs; the last rung stands past it. Measured on v5e silicon
# (PR 42; B1, S16384, bfloat16, 12,288 columns of x; Kimi delta
# attention's 32 + 32 + 32 heads of 128 and, after the stroke, the Gated
# DeltaNet's 16 + 16 + 32 in the leading 8,192; one kernel alone by the
# host's clock, ms; read once and written once the forward is 0.98
# (0.66) at 819 GB/s, the backward 1.47 (1.15)):
#   rows  chunk  heads   forward        backward
#     64    32     1     3.107 / 1.819  4.147 / 2.508
#    128    32     1     2.056 / 1.274  3.022 / 1.897
#    128    16     1     2.069 / 1.288  2.912 / 1.826
#    128   128     1     2.069 / 1.273  3.000 / 1.895
#    256    32     1     1.572 / 1.014  2.469 / 1.606
#    512    32     1     1.447 / 0.983  2.466 / 1.618
#    256    32     2     1.416 / 0.940  2.430 / 1.574
#    256    32     4     1.385 / 0.927  2.412 / 1.590
#    512    32     4     1.397 / 0.945  2.410 / 1.562
#    512    64     2     1.403 / 0.929  2.426 / 1.584
#   1024    32     2     1.435 / 0.950  149 MB of VMEM: refused
#    256    64     4     1.420          2.456     (a later call, in
#    256   128     4     1.419          2.458      which 256/32/4 read
#    256   256     4     1.508          2.613      1.420 and 2.412)
# (the composition in XLA, same call: 16.05 / 11.56 forward, 35.5 / 25.9
# forward and backward). A head's pass through the staging buffer is a
# chain of loads, taps and stores that the next head's cannot overlap
# while both use one buffer: at one head a pass a tile costs 8 us
# whatever its rows, so 64 rows read 3.1 ms and 512 read 1.45; with
# four heads a pass, each in a buffer of its own, the chains run side
# by side and 256 rows do as 512. (Four heads' columns as one array of
# 512 lanes, an earlier call: 2.00 and 3.82 at any rows: the arrays
# spill.) The chunk hardly matters to the kernels and is 128 for the
# program's sake: the chunks of a tile are unrolled as the kernel is
# traced, and at 32 tracing and lowering the two kernels took 3.6 s of
# every start where at 128 they take 0.7 (``setup_s`` warm 39.8 -> 37.5
# in ``ling3flash-injit-1chip``, 34.0 -> 28.5 in
# ``qwen3next-injit-1chip``, whose parent reads 28.4). **The row sums
# read the same on the XLU (``jnp.sum``) and on the MXU** (a product
# with a [128, 128] block of ones, float32 at full precision: 2.005 /
# 1.328 and 3.813 / 2.444 beside 2.001 / 1.323 and 3.815 / 2.427 in
# that earlier call;
# split in three bfloat16 passes: 2.010 and 3.817): the kernels wait
# for loads and stores, not for the sums, and ``jnp.sum`` stays. In
# ``ling3flash-injit-1chip``'s step (traced; the scope ``kda.conv``
# holds the two kernels and nothing else of weight) they read 1.28 ms a
# layer forward and 3.67 under the backward, the forward again and the
# backward, where the composition read 3.5 and 13.5 there and 29.7 more
# under ``kda.rule``.
_ROW_LADDER = ((None, 256, 128, 4),)


def _tile_for(seq: int, segments, rows: Optional[int], chunk: Optional[int],
              group: Optional[int]):
    ladder = next(r for r in _ROW_LADDER if r[0] is None or seq <= r[0])
    rows = ladder[1] if rows is None else int(rows)
    rows = min(rows, -(-seq // _HALO) * _HALO)
    chunk = math.gcd(ladder[2], rows) if chunk is None else int(chunk)
    counts = [s[1] for s in segments if s[1]]
    group = math.gcd(ladder[3], *counts) if group is None else int(group)
    if rows % _HALO or chunk % _HALO or rows % chunk \
            or any(c % group for c in counts):
        raise ValueError(
            f"{rows} rows a tile in chunks of {chunk}, {group} heads a "
            f"pass: multiples of {_HALO}, the chunk dividing the tile, "
            f"the heads a pass dividing q's, k's and v's {counts}")
    return rows, chunk, group


def _note_call(rows: int, columns: int, normalised: int, taps: int) -> None:
    """``hvd_qkv_prologue{kind=...}`` of the call being traced
    (docs/metrics.md)."""
    from horovod_tpu.common import basics
    basics.note_traced(
        "hvd_qkv_prologue",
        "the fused q, k, v prologue traced last: rows a tile, columns a "
        "tile, heads L2-normalised and taps",
        {"tile_rows": rows, "tile_columns": columns,
         "normalised_heads": normalised, "taps": taps})


def _segments(heads: int, normalised: int, scaled: int, key_dim: int):
    """q's, k's and v's (first head, heads, normalised, scale)."""
    return ((0, scaled, True, key_dim ** -0.5),
            (scaled, normalised - scaled, True, 1.0),
            (normalised, heads - normalised, False, 1.0))


# -- inside a tile ----------------------------------------------------------

def _row_sum(x):
    """[rows, 1]: a row's sum over the head's lanes, float32. On the
    XLU; a product with a block of ones on the MXU reads the same to
    the microsecond (the ladder's comment)."""
    return jnp.sum(x, axis=1, keepdims=True)


def _columns(head, dim: int):
    """The ``head``-th head's columns."""
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(head * dim, dim), dim)


def _taps_of(xe_ref, w, at: int, chunk: int, taps: int):
    """The chunk's rows as each tap reads them, ``taps`` arrays [chunk,
    D], and the pre-activation. ``xe_ref`` holds the tile behind
    ``_LEAD`` earlier rows; the chunk starts at the tile's row ``at``."""
    from jax.experimental import pallas as pl
    xs = [xe_ref[pl.ds(_LEAD - (taps - 1) + j + at, chunk), :]
          for j in range(taps)]
    p = w[0:1] * xs[0]
    for j in range(1, taps):
        p = p + w[j:j + 1] * xs[j]
    return xs, p


def _fwd_kernel(x_ref, w_ref, q_ref, k_ref, v_ref, tail_ref, xe_ref, *,
                segments, dim, group, taps, eps, chunk):
    from jax.experimental import pallas as pl
    rows = x_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        tail_ref[...] = jnp.zeros_like(tail_ref)

    for out_ref, (first, count, normalised, scale) in zip(
            (q_ref, k_ref, v_ref), segments):

        def heads(i, _, out_ref=out_ref, first=first, normalised=normalised,
                  scale=scale):
            for slot in range(group):   # independent chains, side by side
                head = i * group + slot
                cols, out_cols = (_columns(first + head, dim),
                                  _columns(head, dim))
                xe = xe_ref.at[slot]
                w = w_ref[:, cols]
                xe[0:_LEAD, :] = tail_ref[:, cols]
                xe[_LEAD:, :] = x_ref[0, :, cols].astype(_F32)
                tail_ref[:, cols] = xe[rows:rows + _LEAD, :]
                for at in range(0, rows, chunk):
                    _, p = _taps_of(xe, w, at, chunk, taps)
                    a = p * jax.nn.sigmoid(p)
                    if normalised:
                        a = a * (jax.lax.rsqrt(_row_sum(a * a) + eps) * scale)
                    out_ref[0, at:at + chunk, out_cols] = a.astype(
                        out_ref.dtype)
            return 0

        if count:
            jax.lax.fori_loop(0, count // group, heads, 0)


def _bwd_kernel(x_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref, dx_ref,
                dw_ref, carry_ref, acc_ref, xe_ref, dpe_ref, *, segments,
                dim, group, taps, eps, chunk, seq):
    from jax.experimental import pallas as pl
    rows = x_ref.shape[1]
    step, n_tiles = pl.program_id(1), pl.num_programs(1)
    tile = n_tiles - 1 - step       # the last tile first
    width = sum(s[1] for s in segments) * dim

    @pl.when(jnp.logical_and(pl.program_id(0) == 0, step == 0))
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(step == 0)
    def _sequence_end():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    if dx_ref.shape[2] > width:     # columns the prologue never read
        dx_ref[0, :, width:] = jnp.zeros(
            (rows, dx_ref.shape[2] - width), dx_ref.dtype)

    ragged = seq % rows != 0        # the last tile ends past the sequence
    inside = lambda at, n: tile * rows + at + jax.lax.broadcasted_iota(
        jnp.int32, (n, 1), 0) < seq
    for dy_ref, (first, count, normalised, scale) in zip(
            (dq_ref, dk_ref, dv_ref), segments):

        def heads(i, _, dy_ref=dy_ref, first=first, normalised=normalised,
                  scale=scale):
            for slot in range(group):   # independent chains, side by side
                head = i * group + slot
                cols, dy_cols = (_columns(first + head, dim),
                                 _columns(head, dim))
                xe, dpe = xe_ref.at[slot], dpe_ref.at[slot]
                w = w_ref[:, cols]
                x = x_ref[0, :, cols].astype(_F32)
                if ragged:
                    x = jnp.where(inside(0, rows), x, 0.0)
                xe[0:_LEAD, :] = jnp.where(
                    tile > 0, halo_ref[0, _HALO - _LEAD:, cols].astype(_F32),
                    0.0)
                xe[_LEAD:, :] = x
                dpe[rows:, :] = carry_ref[:, cols]
                for at in range(0, rows, chunk):
                    xs, p = _taps_of(xe, w, at, chunk, taps)
                    sg = jax.nn.sigmoid(p)
                    g = dy_ref[0, at:at + chunk, dy_cols].astype(_F32)
                    if normalised:
                        a = p * sg
                        r = jax.lax.rsqrt(_row_sum(a * a) + eps)
                        g = (g - a * (r * r * _row_sum(g * a))) * (r * scale)
                    dp = g * (sg * (1.0 + p * (1.0 - sg)))
                    if ragged:
                        dp = jnp.where(inside(at, chunk), dp, 0.0)
                    dpe[at:at + chunk, :] = dp
                    for j in range(taps):
                        acc_ref[j * _LEAD:(j + 1) * _LEAD, cols] += (
                            dp * xs[j]).reshape(
                                chunk // _LEAD, _LEAD, dim).sum(0)
                carry_ref[:, cols] = dpe[0:_LEAD, :]
                for at in range(0, rows, chunk):
                    dx = w[taps - 1:taps] * dpe[at:at + chunk, :]
                    for j in range(taps - 1):
                        dx = dx + w[j:j + 1] * dpe[
                            pl.ds(at + taps - 1 - j, chunk), :]
                    dx_ref[0, at:at + chunk, cols] = dx.astype(dx_ref.dtype)
            return 0

        if count:
            jax.lax.fori_loop(0, count // group, heads, 0)

    @pl.when(jnp.logical_and(pl.program_id(0) == pl.num_programs(0) - 1,
                             step == n_tiles - 1))
    def _finish():
        for j in range(taps):
            dw_ref[j:j + 1, :] = jnp.sum(
                acc_ref[j * _LEAD:(j + 1) * _LEAD, :], axis=0, keepdims=True)


# -- the calls --------------------------------------------------------------

def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=64 << 20)


def _out_shapes(x, segments, dim):
    return tuple(jax.ShapeDtypeStruct((*x.shape[:2], s[1] * dim), x.dtype)
                 for s in segments)


_STATIC = ("dim", "key_dim", "normalised", "scaled", "eps", "rows", "chunk",
           "group", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _prologue_fwd(x, w, dim, key_dim, normalised, scaled, eps, rows, chunk,
                  group, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, seq = x.shape[:2]
    taps, width = w.shape
    segments = _segments(width // dim, normalised, scaled, key_dim)
    outs = _out_shapes(x, segments, dim)
    tiled = lambda cols: pl.BlockSpec((1, rows, cols), lambda b, t: (b, t, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, segments=segments, dim=dim,
                          group=group, taps=taps, eps=eps, chunk=chunk),
        grid=(bt, -(-seq // rows)),
        in_specs=[tiled(width), pl.BlockSpec((taps, width), lambda b, t: (0, 0))],
        out_specs=tuple(tiled(o.shape[2]) for o in outs),
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((_LEAD, width), _F32),
                        pltpu.VMEM((group, _LEAD + rows, dim), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=KERNEL_PREFIX + "_fwd",
        cost_estimate=pl.CostEstimate(
            flops=bt * seq * width * (2 * taps + 8),
            transcendentals=bt * seq * width,
            bytes_accessed=2 * bt * seq * width * x.dtype.itemsize),
    )(x, w)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _prologue_bwd(x, w, dq, dk, dv, dim, key_dim, normalised, scaled, eps,
                  rows, chunk, group, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, seq = x.shape[:2]
    taps, width = w.shape
    n_tiles = -(-seq // rows)
    segments = _segments(width // dim, normalised, scaled, key_dim)
    at = lambda t: n_tiles - 1 - t
    tiled = lambda cols: pl.BlockSpec(
        (1, rows, cols), lambda b, t: (b, at(t), 0))
    whole = pl.BlockSpec((taps, width), lambda b, t: (0, 0))
    before = pl.BlockSpec(
        (1, _HALO, width),
        lambda b, t: (b, jnp.maximum(at(t) * (rows // _HALO) - 1, 0), 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, segments=segments, dim=dim,
                          group=group, taps=taps, eps=eps, chunk=chunk,
                          seq=seq),
        grid=(bt, n_tiles),
        in_specs=[tiled(width), before, whole,
                  *(tiled(d.shape[2]) for d in (dq, dk, dv))],
        out_specs=(tiled(x.shape[2]), whole),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(w.shape, _F32)),
        scratch_shapes=[pltpu.VMEM((_LEAD, width), _F32),
                        pltpu.VMEM((taps * _LEAD, width), _F32),
                        pltpu.VMEM((group, _LEAD + rows, dim), _F32),
                        pltpu.VMEM((group, rows + _LEAD, dim), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=KERNEL_PREFIX + "_bwd",
        cost_estimate=pl.CostEstimate(
            flops=bt * seq * width * (6 * taps + 20),
            transcendentals=bt * seq * width,
            bytes_accessed=(2 * width + x.shape[2]) * bt * seq
            * x.dtype.itemsize),
    )(x, x, w, dq, dk, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(2, 11)))
def _prologue(x, w, *static):
    return _prologue_fwd(x, w, *static)


def _vjp_fwd(x, w, *static):
    return _prologue_fwd(x, w, *static), (x, w)


def _vjp_bwd(*args):
    *static, (x, w), d_out = args
    dx, dw = _prologue_bwd(x, w, *(d.astype(x.dtype) for d in d_out),
                           *static)
    return dx, dw.astype(w.dtype)


_prologue.defvjp(_vjp_fwd, _vjp_bwd)


def qkv_prologue(x, kernel, head_dim: int, normalised_heads: int,
                 scaled_heads: int, eps: float = 1e-6,
                 rows: Optional[int] = None, chunk: Optional[int] = None,
                 group: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 key_dim: Optional[int] = None):
    """``(q, k, v)``, each [B, S, heads x head_dim] in ``x``'s type, of
    the module docstring's chain over the leading ``C`` columns of ``x``
    [B, S, >= C], ``kernel`` [taps, C] float32 the causal depthwise
    convolution's. The columns are heads of ``head_dim``: the first
    ``scaled_heads`` are q's (L2-normalised and scaled by ``head_dim **
    -0.5``), up to ``normalised_heads`` k's (L2-normalised), the rest
    v's. **A head is whole lane tiles** (``head_dim`` a multiple of 128
    where the kernels are compiled; the interpreter takes any): heads
    of another width come laid out, ``x``'s columns and the kernel's
    both (``gated_delta.lay_heads``: 96 channels behind 32 zeros, a
    value head of 192 as two such runs), and say in ``key_dim`` how
    many of a key head's channels are no padding, whose ``-0.5`` power
    q's scale is; a zero channel under zero taps stays zero, in the
    sums and on the way back. ``rows``, ``chunk`` and ``group`` None
    take the ladder's (``_ROW_LADDER``); a length that is no multiple
    of ``rows`` ends in a tile whose rows past it are never written.
    Differentiable in ``x`` and ``kernel``."""
    taps, width = kernel.shape
    if x.ndim != 3 or x.shape[2] < width or width % head_dim \
            or not 0 < taps <= _LEAD + 1 \
            or not 0 <= scaled_heads <= normalised_heads <= width // head_dim:
        raise ValueError(
            f"x{x.shape} kernel{kernel.shape} heads of {head_dim}, "
            f"{normalised_heads} normalised, {scaled_heads} scaled: want "
            f"[B,S,>=C], [taps<={_LEAD + 1},C], C in whole heads (of whole "
            f"lane tiles on the chip: lay narrower ones out), scaled <= "
            f"normalised <= heads")
    key_dim = int(head_dim if key_dim is None else key_dim)
    rows, chunk, group = _tile_for(
        x.shape[1], _segments(width // head_dim, normalised_heads,
                              scaled_heads, key_dim), rows, chunk, group)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _note_call(rows, width, normalised_heads, taps)
    return _prologue(x, kernel.astype(_F32), int(head_dim), key_dim,
                     int(normalised_heads), int(scaled_heads), float(eps),
                     rows, chunk, group, bool(interpret))
