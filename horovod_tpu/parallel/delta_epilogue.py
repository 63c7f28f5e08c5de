"""Pallas epilogue of the delta rules: the way from the rule's kernels
to the output projection in **one pass each way**. What Kimi delta
attention (``models.ling3flash``) and the Gated DeltaNet
(``models.qwen3next``) both do between ``parallel.kda`` /
``parallel.gated_delta`` and ``y W_o``::

    n = o rsqrt(mean_head(o^2) + eps) scale     a head's RMSNorm
    y = cast(n act(z))                          to o's type

``o`` comes in the model's type (bfloat16) as the rule's kernels write
it, ``[B, S, H x D]``, and ``y`` leaves in it; everything between is
float32 in VMEM on a tile that is read once. The gate ``z`` has one of
two forms, told apart by its shape:

* **a head's scalar**, ``[B, S, H]`` float32 (Kimi delta attention's
  ``sigmoid(z_h)``, from ``in_proj_bz``);
* **an element's**, the ``H x D`` columns from ``gate_start`` on of an
  array ``[B, S, >= H x D]`` in any float type (the Gated DeltaNet's
  ``silu(z)``, the last 4,096 of ``qkvz``'s 12,288 bfloat16 columns):
  addressed by the block spec, no slice materialised.

``activation`` (``"sigmoid"`` or ``"silu"``, static) is the caller's to
say; either goes with either form. The rounding points are those of
the composition written out in XLA (``nn.RMSNorm`` in float32 -> the
gate -> the cast): ``o``, ``z`` and ``dy`` widened once, ``y``, ``do``
and an element's ``dz`` rounded once.

Kernel shape: the prologue's (``parallel.qkv_prologue``). Grid
``(batch, tile of rows)``; a step holds a tile's rows of **every**
column and walks the heads, a head's ``D`` columns at a time (whole
lane tiles: 128 in both models that brought the kernels; heads off the
tile come laid out, as ``delta_epilogue`` says), a few
heads a pass so that their chains of loads and stores overlap. No
halo and no staging buffer: every row is its own, and a chunk of rows
goes from the block to registers and back (``_ROW_LADDER`` has the
sweep).

* forward (``delta_epilogue_fwd``): square, the row sum a head,
  ``rsqrt``, the two multiplies, the activation, the cast.
* backward (``delta_epilogue_bwd``): takes ``o``, ``scale``, the gate
  and ``dy``, recomputes a row's ``rsqrt`` and the activation, and
  writes ``do`` in ``o``'s type, ``dz`` (a head's scalar in float32, an
  element's in ``z``'s type, ``[B, S, H x D]``: where ``z`` is part of a
  wider array the caller's gradient is ``dz`` behind zeros, an XLA pad
  that fuses into the add that meets the array's other gradient) and
  ``dscale`` [D] float32, summed in float32 over rows, heads, tiles and
  batch, eight partial rows a slot until the last step. Per backward
  ``o``, ``dy`` and the gate are read once, ``do`` and ``dz`` written
  once.

``delta_epilogue`` is differentiable through the two kernels
(``custom_vjp``) in ``o``, ``scale`` and the gate. **Its output is not
worth keeping across a recomputed block** (134 MB a layer at 16,384
tokens, remade in half a millisecond from ``o``, which the rule's
kernel wrote and the block keeps): ``KERNEL_PREFIX`` is what a remat
policy that keeps kernels' outputs leaves out
(``glm_moe._keep_kernel_outputs``). The kernels' names carry no prefix
of the rules' (``kda_``, ``gdn_``) nor of the prologue's: the
benchmark's readers book kernels by prefix, and this time is not the
recurrence's.

Off the TPU the kernels run in interpreter mode, as every kernel here.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from horovod_tpu.parallel.qkv_prologue import (
    _columns, _compiler_params, _row_sum,
)

_F32 = jnp.float32

# The kernels' names begin with this (a remat policy tells them by it).
KERNEL_PREFIX = "delta_epilogue"

# Rows of a bfloat16 memory tile: a tile's and a chunk's rows come in
# these. Rows of a float32 register tile: ``dscale``'s partial rows.
_TILE = 16
_PART = 8

# ``act(z)`` and ``act'(z)`` from ``z`` and ``s = sigmoid(z)``.
_ACTIVATIONS = {
    "sigmoid": (lambda z, s: s, lambda z, s: s * (1.0 - s)),
    "silu": (lambda z, s: z * s, lambda z, s: s * (1.0 + z * (1.0 - s))),
}

# Rows a tile, rows a chunk and heads a pass of the head loop by
# sequence length, as (longest sequence, rows, chunk, heads) rungs; the
# last rung stands past it. Measured on v5e silicon (PR 43; B1, S16384,
# 32 heads of 128, bfloat16; Kimi delta attention's gate, a head's
# float32 scalar under sigmoid, and after the stroke the Gated
# DeltaNet's, the last 4,096 of 12,288 bfloat16 columns under silu; one
# kernel alone by the host's clock, ms; read once and written once the
# forward is 0.33 (0.49) at 819 GB/s, the backward 0.49 (0.82)):
#   rows  chunk  heads   forward        backward
#     64    64     4     0.579 / 0.696  0.853 / 1.121
#    128   128     4     0.504 / 0.673  0.788 / 1.083
#    256   128     1     0.582 / 0.656  0.873 / 1.092
#    256   128     2     0.504 / 0.660  0.791 / 1.073
#    256   128     4     0.469 / 0.657  0.801 / 1.076
#    256   128     8     0.459 / 0.654  0.778 / 1.075
#    256    64     4     0.467 / 0.658  1.044 / 1.078
#    256   256     4     0.479 / 0.657  0.703 / 1.043
#    512   128     4     0.454 / 0.657  0.779 / 1.081
#    512   128     8     0.451 / 0.653  0.768 / 1.082
#    512   256     4     0.463 / 0.658  0.691 / 1.054
# (the composition in XLA, same call: 5.39 / 3.99 forward, 10.10 / 10.79
# forward and backward). An element's gate reads the same at every
# tile: 1.3 times its memory roofline each way, waiting for loads and
# stores. A head's gate is picked out of a [rows, 32] tile by a masked
# row sum a head and chunk, and its forward gains from more heads a pass
# (0.58 at one, 0.47 at four) where the element's does not; the backward
# reads and rewrites dz's [rows, 32] tile a head, which is why it
# prefers a chunk of a whole tile's rows (0.70 at 256, 0.80 at 128, 1.04
# at 64). **The same gate through the MXU** (a product of the tile with
# a one-hot block, float32 at full precision, bit for bit the same y)
# read 0.687 and 0.823 at 256/128/4: slower, and the row sum stays. 512
# rows gain a hundredth over 256 for twice the VMEM (40 MB of blocks in
# the element's backward) and are left. In ``ling3flash-injit-1chip``'s
# step (traced; the scope ``kda.norm`` holds the two kernels, a slice
# and a concatenation) they read 1.60 ms a layer, the forward twice and
# the backward (1.68 at chunks of 128), where the composition read 12.5.
_ROW_LADDER = ((None, 256, 256, 4),)


def _tile_for(seq: int, heads: int, rows: Optional[int],
              chunk: Optional[int], group: Optional[int]):
    ladder = next(r for r in _ROW_LADDER if r[0] is None or seq <= r[0])
    rows = ladder[1] if rows is None else int(rows)
    rows = min(rows, -(-seq // _TILE) * _TILE)
    chunk = math.gcd(ladder[2], rows) if chunk is None else int(chunk)
    group = math.gcd(ladder[3], heads) if group is None else int(group)
    if rows % _TILE or chunk % _TILE or rows % chunk or heads % group:
        raise ValueError(
            f"{rows} rows a tile in chunks of {chunk}, {group} heads a "
            f"pass: multiples of {_TILE}, the chunk dividing the tile, "
            f"the heads a pass dividing the {heads} heads")
    return rows, chunk, group


def _note_call(rows: int, columns: int, heads: int, per_head: bool) -> None:
    """``hvd_delta_epilogue{kind=...}`` of the call being traced
    (docs/metrics.md)."""
    from horovod_tpu.common import basics
    basics.note_traced(
        "hvd_delta_epilogue",
        "the fused gated head norm traced last: rows a tile, columns a "
        "tile, heads, and whether the gate is a head's scalar (1) or an "
        "element's (0)",
        {"tile_rows": rows, "tile_columns": columns, "heads": heads,
         "gate_per_head": int(per_head)})


# -- inside a tile ----------------------------------------------------------

def _per_head(o, z, dim: int) -> bool:
    """Whether the gate ``z`` is a head's scalar, by its shape."""
    return z.shape[2] == o.shape[2] // dim != o.shape[2]


def _lane_is(head, shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) == head


def _gate(z_ref, at_rows, head, cols, per_head: bool):
    """The chunk's gate in float32: [rows, 1] of a tile of heads'
    scalars [rows, H], or the head's columns of an element's. A head is
    a loop's index and a lane cannot be picked by one: the other lanes
    are masked out of a row sum."""
    if not per_head:
        return z_ref[0, at_rows, cols].astype(_F32)
    z = z_ref[0, at_rows, :].astype(_F32)
    return _row_sum(jnp.where(_lane_is(head, z.shape), z, 0.0))


def _fwd_kernel(o_ref, w_ref, z_ref, y_ref, *, dim, filled, group, eps,
                chunk, activation, per_head):
    rows = o_ref.shape[1]
    act, _ = _ACTIVATIONS[activation]
    w = w_ref[...]

    def heads(i, _):
        for slot in range(group):   # independent chains, side by side
            head = i * group + slot
            cols = _columns(head, dim)
            for at in range(0, rows, chunk):
                at_rows = slice(at, at + chunk)
                x = o_ref[0, at_rows, cols].astype(_F32)
                z = _gate(z_ref, at_rows, head, cols, per_head)
                r = jax.lax.rsqrt(_row_sum(x * x) / filled + eps)
                y_ref[0, at_rows, cols] = (
                    x * (r * w) * act(z, jax.nn.sigmoid(z))).astype(
                        y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, o_ref.shape[2] // dim // group, heads, 0)


def _bwd_kernel(o_ref, w_ref, z_ref, dy_ref, do_ref, dz_ref, dw_ref, acc_ref,
                *, dim, filled, group, eps, chunk, activation, per_head, seq):
    from jax.experimental import pallas as pl
    rows = o_ref.shape[1]
    act, dact = _ACTIVATIONS[activation]
    w = w_ref[...]
    tile = pl.program_id(1)
    first = jnp.logical_and(pl.program_id(0) == 0, tile == 0)
    last = jnp.logical_and(pl.program_id(0) == pl.num_programs(0) - 1,
                           tile == pl.num_programs(1) - 1)

    @pl.when(first)
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ragged = seq % rows != 0        # the last tile ends past the sequence
    inside = lambda at: tile * rows + at + jax.lax.broadcasted_iota(
        jnp.int32, (chunk, 1), 0) < seq

    def heads(i, _):
        for slot in range(group):   # independent chains, side by side
            head = i * group + slot
            cols = _columns(head, dim)
            for at in range(0, rows, chunk):
                at_rows = slice(at, at + chunk)
                x = o_ref[0, at_rows, cols].astype(_F32)
                g = dy_ref[0, at_rows, cols].astype(_F32)
                z = _gate(z_ref, at_rows, head, cols, per_head)
                s = jax.nn.sigmoid(z)
                a, da = act(z, s), dact(z, s)
                r = jax.lax.rsqrt(_row_sum(x * x) / filled + eps)
                xr, gw = x * r, g * w
                u = gw * xr                     # dy n, less the gate
                if per_head:
                    m = _row_sum(u)
                    dx = (r * a) * (gw - xr * (m / filled))
                    dz_ref[0, at_rows, :] = jnp.where(
                        _lane_is(head, (chunk, dz_ref.shape[2])),
                        (m * da).astype(dz_ref.dtype), dz_ref[0, at_rows, :])
                else:
                    dx = r * (gw * a - xr * (_row_sum(u * a) / filled))
                    dz_ref[0, at_rows, cols] = (u * da).astype(dz_ref.dtype)
                do_ref[0, at_rows, cols] = dx.astype(do_ref.dtype)
                dw = g * xr * a
                if ragged:
                    dw = jnp.where(inside(at), dw, 0.0)
                acc_ref[slot * _PART:(slot + 1) * _PART, :] += dw.reshape(
                    chunk // _PART, _PART, dim).sum(0)
        return 0

    jax.lax.fori_loop(0, o_ref.shape[2] // dim // group, heads, 0)

    @pl.when(last)
    def _finish():
        dw_ref[...] = jnp.sum(acc_ref[...], axis=0, keepdims=True)


# -- the calls --------------------------------------------------------------

def _specs(o, z, dim, start, rows):
    """(a tile of ``o``'s shape, the scale's, the gate's tile, whether
    the gate is a head's scalar)."""
    from jax.experimental import pallas as pl
    width = o.shape[2]
    per_head = _per_head(o, z, dim)
    tiled = pl.BlockSpec((1, rows, width), lambda b, t: (b, t, 0))
    whole = pl.BlockSpec((1, dim), lambda b, t: (0, 0))
    gate = pl.BlockSpec((1, rows, z.shape[2]), lambda b, t: (b, t, 0)) \
        if per_head else pl.BlockSpec(
            (1, rows, width), lambda b, t: (b, t, start // width))
    return tiled, whole, gate, per_head


_STATIC = ("dim", "filled", "activation", "start", "eps", "rows", "chunk",
           "group", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _epilogue_fwd(o, w, z, dim, filled, activation, start, eps, rows, chunk,
                  group, interpret):
    from jax.experimental import pallas as pl
    bt, seq, width = o.shape
    tiled, whole, gate, per_head = _specs(o, z, dim, start, rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, dim=dim, filled=filled, group=group,
                          eps=eps, chunk=chunk, activation=activation,
                          per_head=per_head),
        grid=(bt, -(-seq // rows)),
        in_specs=[tiled, whole, gate],
        out_specs=tiled,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=KERNEL_PREFIX + "_fwd",
        cost_estimate=pl.CostEstimate(
            flops=bt * seq * width * 10,
            transcendentals=bt * seq * (width // dim if per_head else width),
            bytes_accessed=bt * seq * (
                2 * width * o.dtype.itemsize + gate.block_shape[2]
                * z.dtype.itemsize)),
    )(o, w, z)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _epilogue_bwd(o, w, z, dy, dim, filled, activation, start, eps, rows,
                  chunk, group, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, seq, width = o.shape
    tiled, whole, gate, per_head = _specs(o, z, dim, start, rows)
    dz = jax.ShapeDtypeStruct((bt, seq, gate.block_shape[2]), z.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dim=dim, filled=filled, group=group,
                          eps=eps, chunk=chunk, activation=activation,
                          per_head=per_head, seq=seq),
        grid=(bt, -(-seq // rows)),
        in_specs=[tiled, whole, gate, tiled],
        out_specs=(tiled, pl.BlockSpec((1, rows, dz.shape[2]),
                                       lambda b, t: (b, t, 0)), whole),
        out_shape=(jax.ShapeDtypeStruct(o.shape, o.dtype), dz,
                   jax.ShapeDtypeStruct(w.shape, _F32)),
        scratch_shapes=[pltpu.VMEM((group * _PART, dim), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=KERNEL_PREFIX + "_bwd",
        cost_estimate=pl.CostEstimate(
            flops=bt * seq * width * 24,
            transcendentals=bt * seq * (width // dim if per_head else width),
            bytes_accessed=bt * seq * (
                3 * width * o.dtype.itemsize + 2 * dz.shape[2]
                * z.dtype.itemsize)),
    )(o, w, z, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 12)))
def _epilogue(o, w, z, *static):
    return _epilogue_fwd(o, w, z, *static)


def _vjp_fwd(o, w, z, *static):
    return _epilogue_fwd(o, w, z, *static), (o, w, z)


def _vjp_bwd(*args):
    *static, (o, w, z), dy = args
    do, dz, dw = _epilogue_bwd(o, w, z, dy.astype(o.dtype), *static)
    if dz.shape != z.shape:     # z's columns of a wider array
        start = static[3]
        dz = jnp.pad(dz, ((0, 0), (0, 0),
                          (start, z.shape[2] - start - dz.shape[2])))
    return do, dw, dz


_epilogue.defvjp(_vjp_fwd, _vjp_bwd)


def delta_epilogue(o, scale, gate, head_dim: int, activation: str,
                   gate_start: int = 0, eps: float = 1e-6,
                   rows: Optional[int] = None, chunk: Optional[int] = None,
                   group: Optional[int] = None,
                   interpret: Optional[bool] = None,
                   filled: Optional[int] = None):
    """``y`` [B, S, H x head_dim] in ``o``'s type of the module
    docstring's chain: ``o`` [B, S, H x head_dim] normalised a head
    (RMS), times ``scale`` [head_dim] float32, times ``activation``
    (``"sigmoid"`` or ``"silu"``) of the gate. ``gate`` [B, S, H] is a
    head's scalar; any other is an element's, in the ``H x head_dim``
    columns from ``gate_start`` (a multiple of that width) on. **A
    head is whole lane tiles** (``head_dim`` a multiple of 128 where
    the kernels are compiled; the interpreter takes any): heads of
    another width come laid out, ``o``, ``scale`` and an element's gate
    alike (``gated_delta.lay_heads``: a head of 192 as two runs of 96
    channels behind 32 zeros each, 256 columns), and say in ``filled``
    how many of a head's channels are no padding, the mean's divisor; a
    zero channel under a zero scale stays zero, in ``y`` and on the way
    back. ``rows``, ``chunk`` and ``group`` None take the ladder's
    (``_ROW_LADDER``); a length that is no multiple of ``rows`` ends in
    a tile whose rows past it are never written. Differentiable in
    ``o``, ``scale`` and ``gate``."""
    width = o.shape[2] if o.ndim == 3 else 0
    heads = width // head_dim
    per_head = bool(width) and gate.ndim == 3 and _per_head(o, gate, head_dim)
    if o.ndim != 3 or not width or width % head_dim \
            or scale.shape != (head_dim,) or activation not in _ACTIVATIONS \
            or gate.ndim != 3 or gate.shape[:2] != o.shape[:2] \
            or not (per_head and gate_start == 0 or (
                gate_start % width == 0
                and 0 <= gate_start <= gate.shape[2] - width)):
        raise ValueError(
            f"o{o.shape} scale{scale.shape} gate{gate.shape} from column "
            f"{gate_start} under {activation!r}, heads of {head_dim}: want "
            f"[B,S,HxD], [D], and [B,S,H] or [B,S,>=HxD] from a multiple "
            f"of HxD on, under one of {sorted(_ACTIVATIONS)}; D in whole "
            f"lane tiles on the chip (lay narrower heads out)")
    rows, chunk, group = _tile_for(o.shape[1], heads, rows, chunk, group)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _note_call(rows, width, heads, per_head)
    return _epilogue(o, scale.astype(_F32).reshape(1, head_dim), gate,
                     int(head_dim), int(filled or head_dim), activation,
                     int(gate_start), float(eps),
                     rows, chunk, group, bool(interpret))
