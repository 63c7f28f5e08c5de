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
- causal skip, by tile and inside it: a kv tile entirely in the future
  is predicated off with `pl.when`; a tile wholly at or under the
  diagonal runs one unmasked body; a tile the diagonal crosses runs a
  body specialised to where it crosses (`_over_tile`), which computes
  only the [sub_q, sub_k] sub-tiles the mask leaves and masks only the
  ones the diagonal crosses (`_diagonal_blocks`). The DMA tile and the
  grid stay as large as the ladder measured them; at 512x1024 tiles the
  kernels compute 56% of the square at S=2048 and 53% at S=4096 where
  the whole-tile predicate computed 75% and 62.5% (the causal need is
  50%; `causal_subtile_counts` gives the number for any call);
- a ``window`` is a second diagonal, ``window`` keys behind the first:
  tiles wholly behind it are skipped like those above the causal one,
  and not even walked: a windowed call's grid has only the steps the
  band can touch (``_streamed_tiles``: three a q tile at 1024x1024
  tiles and a window of 512, where the causal grid has sixteen at
  S=16384), each step's kv tile computed from the runtime offsets;
- k and v may have fewer heads than q (index maps send a group of
  query heads to one key-value head; the dk/dv kernel's inner grid
  axis runs over the group's q tiles, so dk and dv are summed in its
  scratch and nothing is repeated in HBM), and v a head size of its
  own;
- `offsets` is a runtime int32[2] (scalar-prefetch, SMEM): the global
  positions of q[0] and k[0]. Ring attention passes traced offsets for
  its rotated kv blocks — no retrace per ring step: the case is chosen
  at run time among static bodies. A crossing that does not lie on the
  tiles' own grid (offsets that are no multiple of the tiles) takes
  the whole-tile masked body.

Backward is a pair of pallas kernels (the FlashAttention-2 split):
- dq kernel, grid (BH, q blocks, kv blocks): recomputes each p-block
  from (q, k, lse), forms ds = p * (dp - delta) and accumulates
  dq += ds @ k in fp32 scratch;
- dk/dv kernel, grid (BH, kv blocks, q blocks): same recompute per
  tile, accumulates dv += pᵀ @ do and dk += dsᵀ @ q.
Both take the blocks of a tile from the same `_over_tile` as the
forward.
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
import math
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _diagonal_positions(block_q: int, block_k: int):
    """The positions ``d = q_start - k_start`` of a tile the diagonal
    crosses that have a body of their own: those on the grid the tiles
    themselves make (self-attention and a ring's shards, whose offsets
    are multiples of both tiles, put every tile there): two at 512x1024
    tiles, one at square ones. At ``d`` the tile's first row sees its
    first ``d + 1`` columns."""
    step = math.gcd(block_q, block_k)
    at = tuple(range(-(block_q // step - 1) * step, block_k - 1, step))
    # Each is one more body in all three kernels: tiles far from each
    # other's multiple (explicit odd blocks) keep the whole-tile mask.
    return at if len(at) <= 4 else ()


def _diagonal_blocks(d: int, block_q: int, block_k: int, sub_q: int,
                     sub_k: int):
    """What is left of a tile at position ``d`` once each
    [sub_q, sub_k] sub-tile wholly above the diagonal is dropped: one
    block ``(row_lo, row_hi, free, vis)`` per chunk of ``sub_q`` rows
    that sees anything, over the tile's columns ``[0, vis)``; the
    sub-tiles in ``[0, free)`` lie wholly at or under the diagonal (no
    mask), those in ``[free, vis)`` are crossed by it (masked)."""
    blocks = []
    for r0 in range(0, block_q, sub_q):
        seen = d + r0 + sub_q       # columns the chunk's last row sees
        vis = min(block_k, max(0, -(-seen // sub_k) * sub_k))
        free = min(vis, max(0, (d + r0 + 1) // sub_k * sub_k))
        if vis:
            blocks.append((r0, r0 + sub_q, free, vis))
    return blocks


def _window_positions(block_q: int, block_k: int, window: int):
    """As ``_diagonal_positions`` for a call with a window: the
    positions on the tiles' own grid at which either diagonal (the
    causal one, or the window's ``window`` keys behind it) crosses the
    tile. Three at 512x1024 tiles and a window of 512."""
    step = math.gcd(block_q, block_k)
    at = tuple(d for d in range(-(block_q // step - 1) * step,
                                window + block_k - 1, step)
               if not block_k - 1 <= d <= window - block_q)
    return at if len(at) <= 6 else ()


def _window_blocks(d: int, block_q: int, block_k: int, sub_q: int,
                   sub_k: int, window: int):
    """``_diagonal_blocks`` between two diagonals: per chunk of
    ``sub_q`` rows one block ``(row_lo, row_hi, free, vis, lo)`` over
    the tile's columns ``[lo, vis)``, the sub-tiles any of its rows can
    see (a row sees the ``window`` keys up to its own). A chunk that
    sees all of them carries no mask (``free == vis``); any other is
    masked over all its columns (``free == lo``): a window's layer is a
    thin band, and its two masks are not worth two more bodies."""
    blocks = []
    for r0 in range(0, block_q, sub_q):
        oldest = d + r0 - window + 1        # of the chunk's first row
        newest = d + r0 + sub_q - 1         # of its last row
        lo = min(block_k, max(0, oldest // sub_k * sub_k))
        vis = min(block_k, max(0, -(-(newest + 1) // sub_k) * sub_k))
        if vis <= lo:
            continue
        whole = d + r0 >= vis - 1 and newest - lo < window
        blocks.append((r0, r0 + sub_q, vis if whole else lo, vis, lo))
    return blocks


def _tile_blocks(d: int, block_q: int, block_k: int, sub_q: int,
                 sub_k: int, window=None):
    """The blocks of scores the causal kernels compute in a tile at
    position ``d``, by the rule ``_over_tile`` applies to the runtime
    offsets: nothing above the diagonal (nor, with a ``window``, behind
    it), the whole tile unmasked between them, ``_diagonal_blocks`` (or
    ``_window_blocks``) where one crosses on the grid, and the whole
    tile masked (every sub-tile pays) where it crosses off it."""
    if d <= -block_q or (window is not None
                         and d >= window + block_k - 1):
        return []
    if d >= block_k - 1 and (window is None or d <= window - block_q):
        return [(0, block_q, block_k, block_k)]
    if window is not None:
        if d in _window_positions(block_q, block_k, window):
            return _window_blocks(d, block_q, block_k, sub_q, sub_k, window)
    elif d in _diagonal_positions(block_q, block_k):
        return _diagonal_blocks(d, block_q, block_k, sub_q, sub_k)
    return [(0, block_q, 0, block_k)]


def _over_tile(offs_ref, qi, j, run, *, block_q: int, block_k: int,
               sub_q: int, sub_k: int, causal: bool, window=None):
    """``run(blocks, d)`` on the blocks of scores the (q tile ``qi``, kv
    tile ``j``) pair owes, chosen from what the kernel observes: the
    static ``causal``, ``window`` and tile shapes and the runtime
    offsets in SMEM (``_tile_blocks`` is the same rule on plain
    integers). Each case is a straight-line body of static slices, so
    one compilation serves every offset; ``d`` is the position the
    masks are built from."""
    from jax.experimental import pallas as pl

    unmasked = [(0, block_q, block_k, block_k)]
    if not causal:
        run(unmasked, None)
        return
    d = (offs_ref[0] + qi * block_q) - (offs_ref[1] + j * block_k)
    if window is None:
        pl.when(d >= block_k - 1)(lambda: run(unmasked, None))
        off_grid = jnp.logical_and(d > -block_q, d < block_k - 1)
        bodies = [(at, _diagonal_blocks(at, block_q, block_k, sub_q, sub_k))
                  for at in _diagonal_positions(block_q, block_k)]
    else:
        if block_k - 1 <= window - block_q:     # a tile fits between them
            pl.when(jnp.logical_and(d >= block_k - 1,
                                    d <= window - block_q))(
                lambda: run(unmasked, None))
        off_grid = jnp.logical_and(
            jnp.logical_and(d > -block_q, d < window + block_k - 1),
            jnp.logical_or(d < block_k - 1, d > window - block_q))
        bodies = [(at, _window_blocks(at, block_q, block_k, sub_q, sub_k,
                                      window))
                  for at in _window_positions(block_q, block_k, window)]
    for at, blocks in bodies:
        pl.when(d == at)(lambda at=at, blocks=blocks: run(blocks, at))
        off_grid = jnp.logical_and(off_grid, d != at)
    pl.when(off_grid)(lambda: run([(0, block_q, 0, block_k)], d))


def _block_lo(block) -> int:
    """A block's first column: its fifth member, 0 where it has none."""
    return block[4] if len(block) > 4 else 0


def _block_slices(block):
    """(rows of the q tile, columns of the kv tile) a block spans."""
    r0, r1, _, vis = block[:4]
    return slice(r0, r1), slice(_block_lo(block), vis)


def _tail_mask(d, block, window=None):
    """The allowed scores among a block's masked columns
    ``[free, vis)``, or None where it has none."""
    r0, r1, free, vis = block[:4]
    if free == vis:
        return None
    # the block's first row lies d + r0 - free after its first masked column
    ahead = d + r0 - free \
        + jax.lax.broadcasted_iota(jnp.int32, (r1 - r0, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, vis - free), 1)
    if window is None:
        return ahead >= cols
    return jnp.logical_and(ahead >= cols, ahead - cols < window)


def _where_tail(mask, x, fill):
    """``x`` with ``fill`` where ``mask`` (over its last columns)
    forbids; the columns before them are not touched."""
    if mask is None:
        return x
    free = x.shape[1] - mask.shape[1]
    tail = jnp.where(mask, x[:, free:], fill)
    return jnp.concatenate([x[:, :free], tail], axis=1) if free else tail


def _attend(q, k, v, carry, scale: float, mask):
    """One online-softmax update of ``(m, l, acc)`` by a [rows, cols]
    block of scores; ``mask`` (``_tail_mask``) covers its last columns,
    None where every score is allowed."""
    m_prev, l_prev, acc = carry
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = _where_tail(mask, jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale, _NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = _where_tail(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def _floor_div(x, n: int):
    """``x // n`` rounded down for a traced int32 of either sign."""
    return jax.lax.div(jnp.where(x >= 0, x, x - (n - 1)), jnp.int32(n))


def _streamed_tiles(window, block_o: int, block_s: int, num_s: int,
                    behind: bool):
    """How a kernel walks the tiles it streams (kv tiles past a q tile
    in the forward and dq kernels, q tiles past a kv tile in dk/dv):
    ``(steps, tile, fetch)``. Without a window the grid has one step a
    tile and the step is the tile, as ever. With one, only the
    ``steps`` consecutive tiles the band can touch are walked:
    ``tile(o, step, offs)`` is the tile of a step (``o`` the outer
    tile, ``offs`` the runtime offsets) and ``fetch`` the block index
    its DMA asks for, held at the last tile that owes anything so that
    a step behind it moves nothing. ``behind``: the streamed tiles are
    keys, which lie behind their queries; else queries, ahead of their
    keys."""
    if window is None:
        return num_s, (lambda o, step, offs: step), \
            (lambda o, step, offs: step)
    steps = min(num_s, (block_o + window + block_s - 3) // block_s + 1)

    def span(o, offs):
        if behind:      # o a q tile: keys [start - window + 1, end]
            start = offs[0] + o * block_o - offs[1]
            lo, hi = start - (window - 1), start + block_o - 1
        else:           # o a kv tile: queries [start, end + window - 1]
            start = offs[1] + o * block_o - offs[0]
            lo, hi = start, start + block_o - 1 + window - 1
        first = jnp.clip(_floor_div(lo, block_s), 0, num_s - steps)
        last = jnp.clip(_floor_div(hi, block_s), first, num_s - 1)
        return first, last

    def tile(o, step, offs):
        return span(o, offs)[0] + step

    def fetch(o, step, offs):
        first, last = span(o, offs)
        return jnp.minimum(first + step, last)

    return steps, tile, fetch


def _kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            m_scr, l_scr, acc_scr, *, steps: int, kv_tile, scale: float,
            **tile):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    step = pl.program_id(2)
    j = kv_tile(qi, step, offs_ref)

    @pl.when(step == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def run(blocks, d):
        for block in blocks:
            rows, cols = _block_slices(block)
            m_scr[rows], l_scr[rows], acc_scr[rows] = _attend(
                q_ref[0, rows, :], k_ref[0, cols, :], v_ref[0, cols, :],
                (m_scr[rows], l_scr[rows], acc_scr[rows]), scale,
                _tail_mask(d, block, tile.get("window")))

    _over_tile(offs_ref, qi, j, run, **tile)

    @pl.when(step == steps - 1)
    def _():
        l = l_scr[:]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        # Stats leave as [1, BQ] rows: the HBM stats tensors are
        # [BH, 1, S] so the TPU (8,128) tiling pads the size-1 dim
        # 8x instead of padding a trailing size-1 lane dim 128x.
        m_ref[0] = jnp.transpose(m_scr[:])
        l_ref[0] = jnp.transpose(l)


def _executed_share(seq_q, seq_k, block_q, block_k, sub, causal,
                    window=None):
    """Share of the [Sq, Sk] square the kernels execute, for their
    ``cost_estimate``: the sub-tiles computed at zero offsets (the
    offsets are runtime values; a ring's steps average the same)."""
    if not causal:
        return 1.0
    n = causal_subtile_counts(seq_q, seq_k, block_q, block_k, sub,
                              window=window)
    return n["computed"] / (n["computed"] + n["skipped"])


def _kv_row(group: int):
    """The row of k and v (``[B * kv heads, S, D]``) that row ``b`` of q
    (``[B * q heads, S, D]``) reads: ``group`` consecutive query heads
    share one key-value head, and nothing is repeated in HBM."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret",
                              "sub", "window", "out_dtype"))
def _flash_bhsd(q, k, v, offsets, causal: bool, block_q: int,
                block_k: int, interpret: bool, sub=None, window=None,
                out_dtype=None):
    """q: [BH, Sq, D]; k: [BHkv, Sk, D]; v: [BHkv, Sk, Dv], BH a
    multiple of BHkv; offsets: int32[2]. Returns (o [BH,Sq,Dv],
    m [BH,1,Sq], l [BH,1,Sq]). ``sub`` is the causal sub-tile (rows,
    columns); None takes the ladder's. ``window``: a row sees its own
    key and the ``window - 1`` before it. ``out_dtype``: of ``o``
    (None: q's), written from the float32 accumulator."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, d = q.shape
    seq_k, dv = k.shape[1], v.shape[2]
    out_dtype = q.dtype if out_dtype is None else out_dtype
    scale = 1.0 / (d ** 0.5)
    num_k = seq_k // block_k
    sub_q, sub_k = sub = sub or _subtile_for(d, block_q, block_k)
    share = _executed_share(seq_q, seq_k, block_q, block_k, sub, causal,
                            window)
    kv_row = _kv_row(bh // k.shape[0])
    steps, kv_tile, kv_fetch = _streamed_tiles(
        window, block_q, block_k, num_k, behind=True)

    tile = dict(block_q=block_q, block_k=block_k, sub_q=sub_q,
                sub_k=sub_k, causal=causal)
    if window is not None:
        tile["window"] = window
    kernel = functools.partial(
        _kernel, steps=steps, kv_tile=kv_tile, scale=scale, **tile)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, seq_q // block_q, steps),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, offs: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j, offs:
                         (kv_row(b), kv_fetch(i, j, offs), 0)),
            pl.BlockSpec((1, block_k, dv), lambda b, i, j, offs:
                         (kv_row(b), kv_fetch(i, j, offs), 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, dv), lambda b, i, j, offs: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j, offs: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j, offs: (b, 0, i)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((bh, seq_q, dv), out_dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ),
        interpret=interpret,
        name="flash_fwd",
        cost_estimate=pl.CostEstimate(
            flops=int(2 * bh * seq_q * seq_k * (d + dv) * share),
            bytes_accessed=(q.size + bh * seq_q * dv + k.size + v.size)
            * q.dtype.itemsize,
            transcendentals=int(bh * seq_q * seq_k * share),
        ),
    )(offsets, q, k, v)


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    block, d, scale: float, window=None):
    """Shared backward recompute for one block of scores (rows of the
    q tile against columns of the kv tile, the last of them masked):
    p = exp(s - lse) and ds = p · (dp − delta) · scale. The dq
    and dk/dv kernels differ only in what they contract these with."""
    rows, cols = _block_slices(block)
    q = q_ref[0, rows, :].astype(jnp.float32)
    k = k_ref[0, cols, :].astype(jnp.float32)
    v = v_ref[0, cols, :].astype(jnp.float32)
    do = do_ref[0, rows, :].astype(jnp.float32)
    lse = jnp.transpose(lse_ref[0, :, rows])          # [1,R] -> [R,1]
    delta = jnp.transpose(delta_ref[0, :, rows])      # [1,R] -> [R,1]
    s = _where_tail(_tail_mask(d, block, window), jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale, _NEG_INF)  # [R, C]
    # Dead rows (l == 0) store lse = +inf -> p underflows to 0.
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [R, C]
    ds = p * (dp - delta) * scale
    return q, k, do, p, ds


def _bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr, *, steps: int, kv_tile,
                   scale: float, **tile):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    step = pl.program_id(2)
    j = kv_tile(qi, step, offs_ref)

    @pl.when(step == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def run(blocks, d):
        for block in blocks:
            _, k, _, _, ds = _recompute_p_ds(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, block, d,
                scale, tile.get("window"))
            dq_scr[_block_slices(block)[0], :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _over_tile(offs_ref, qi, j, run, **tile)

    @pl.when(step == steps - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    steps: int, q_tile, scale: float, **tile):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)      # kv block (outer)
    step = pl.program_id(2)   # q blocks stream (inner), a group's heads
    qi = q_tile(j, step, offs_ref)  # one after another

    @pl.when(step == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def run(blocks, d):
        for block in blocks:
            q, _, do, p, ds = _recompute_p_ds(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, block, d,
                scale, tile.get("window"))
            cols = _block_slices(block)[1]
            dv_scr[cols, :] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [C, Dv]
            dk_scr[cols, :] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [C, D]

    _over_tile(offs_ref, qi, j, run, **tile)

    @pl.when(step == steps - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret",
                              "sub", "window"))
def _flash_bwd_bhsd(q, k, v, do, lse, delta, offsets, causal: bool,
                    block_q: int, block_k: int, interpret: bool,
                    sub=None, window=None):
    """Backward kernels. q: [BH,Sq,D]; do: [BH,Sq,Dv]; k: [BHkv,Sk,D];
    v: [BHkv,Sk,Dv]; lse, delta: [BH,1,Sq] fp32. Returns (dq, dk, dv)
    in input dtypes; dk and dv are summed over the query heads that
    share a key-value head inside the dk/dv kernel's grid. ``sub`` and
    ``window`` as for ``_flash_bhsd``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_q, d = q.shape
    seq_k, dv = k.shape[1], v.shape[2]
    group = bh // k.shape[0]
    num_q = seq_q // block_q
    num_k = seq_k // block_k
    sub_q, sub_k = sub = sub or _subtile_for(d, block_q, block_k)
    share = _executed_share(seq_q, seq_k, block_q, block_k, sub, causal,
                            window)
    tile = dict(block_q=block_q, block_k=block_k, sub_q=sub_q,
                sub_k=sub_k, causal=causal, scale=1.0 / (d ** 0.5))
    if window is not None:
        tile["window"] = window
    kv_row = _kv_row(group)
    steps, kv_tile, kv_fetch = _streamed_tiles(
        window, block_q, block_k, num_k, behind=True)

    def q_spec(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda b, i, j, offs: (b, i, 0))

    def k_spec(width):
        return pl.BlockSpec((1, block_k, width), lambda b, i, j, offs:
                            (kv_row(b), kv_fetch(i, j, offs), 0))

    stat_spec = pl.BlockSpec((1, 1, block_q),
                             lambda b, i, j, offs: (b, 0, i))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, steps=steps, kv_tile=kv_tile,
                          **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, num_q, steps),
            in_specs=[q_spec(d), k_spec(d), k_spec(dv), q_spec(dv),
                      stat_spec, stat_spec],
            out_specs=q_spec(d),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
        cost_estimate=pl.CostEstimate(
            flops=int(2 * bh * seq_q * seq_k * (2 * d + dv) * share),
            bytes_accessed=(2 * q.size + k.size + v.size)
            * q.dtype.itemsize,
            transcendentals=int(bh * seq_q * seq_k * share),
        ),
    )(offsets, q, k, v, do, lse, delta)

    # dk/dv: swap grid so the kv block is outer and q streams: the q
    # tiles of each of the group's query heads in turn, into one
    # accumulator a key-value head.
    q_steps, q_tile, q_fetch = _streamed_tiles(
        window, block_k, block_q, num_q, behind=False)
    if group == 1:
        q_row, q_of = (lambda b, step: b), (lambda step: step)
    else:
        q_row = lambda b, step: b * group + step // q_steps
        q_of = lambda step: step % q_steps

    def q_spec2(width):
        return pl.BlockSpec((1, block_q, width), lambda b, j, i, offs:
                            (q_row(b, i), q_fetch(j, q_of(i), offs), 0))

    def k_spec2(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda b, j, i, offs: (b, j, 0))

    stat_spec2 = pl.BlockSpec((1, 1, block_q), lambda b, j, i, offs:
                              (q_row(b, i), 0, q_fetch(j, q_of(i), offs)))
    dk, dv_ = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, steps=group * q_steps,
            q_tile=lambda j, step, offs: q_tile(j, q_of(step), offs),
            **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k.shape[0], num_k, group * q_steps),
            in_specs=[q_spec2(d), k_spec2(d), k_spec2(dv), q_spec2(dv),
                      stat_spec2, stat_spec2],
            out_specs=(k_spec2(d), k_spec2(dv)),
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, dv), jnp.float32)],
        ),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        interpret=interpret,
        name="flash_bwd_dkv",
        cost_estimate=pl.CostEstimate(
            flops=int(2 * bh * seq_q * seq_k * (3 * d + 2 * dv) * share),
            bytes_accessed=(q.size + 2 * (k.size + v.size))
            * q.dtype.itemsize,
            transcendentals=int(bh * seq_q * seq_k * share),
        ),
    )(offsets, q, k, v, do, lse, delta)
    return dq, dk, dv_


def causal_subtile_counts(seq_q: int, seq_k: int, block_q: int,
                          block_k: int, sub, q_offset: int = 0,
                          k_offset: int = 0, window=None) -> dict:
    """How often the causal structure engages in one head of a call:
    of the (seq_q / sub_q) x (seq_k / sub_k) sub-tiles of the square,
    how many the kernels compute (``computed``), how many of those pay
    for the mask (``masked``) and how many are not touched
    (``skipped``). ``sub=(block_q, block_k)`` is the whole-tile
    predicate the kernels had before PR 30; ``window`` counts a
    windowed call. Plain integers through ``_tile_blocks``, the rule
    the kernels apply to their runtime offsets."""
    sub_q, sub_k = sub
    if block_q % sub_q or block_k % sub_k or seq_q % block_q \
            or seq_k % block_k:
        raise ValueError(
            f"sub-tile {sub} must divide the tile ({block_q}, {block_k}) "
            f"and the tile the sequences ({seq_q}, {seq_k})")
    computed = masked = 0
    for q_start in range(q_offset, q_offset + seq_q, block_q):
        for k_start in range(k_offset, k_offset + seq_k, block_k):
            for block in _tile_blocks(q_start - k_start, block_q, block_k,
                                      sub_q, sub_k, window):
                r0, r1, free, vis = block[:4]
                computed += (r1 - r0) // sub_q \
                    * ((vis - _block_lo(block)) // sub_k)
                masked += (r1 - r0) // sub_q * ((vis - free) // sub_k)
    return {"computed": computed, "masked": masked,
            "skipped": (seq_q // sub_q) * (seq_k // sub_k) - computed}


def _dense_reference(q, k, v, causal: bool, q_offset, k_offset,
                     window=None, out_dtype=None):
    """Mathematically identical dense formulation (fp32 softmax) — the
    shape fallback and the test oracle for the kernels. Fewer key-value
    heads than query heads are repeated here, which the kernels never
    do."""
    d = q.shape[-1]
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(d))
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        allowed = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            allowed &= q_pos[:, None] - k_pos[None, :] < window
        logits = jnp.where(allowed[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if causal:
        probs = jnp.where(allowed[None, None], probs, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v,
                      preferred_element_type=out_dtype)


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
#
# Measured at D=64 on v5e silicon (PR 31: B1, 20 query heads over 10
# key-value heads, S16384, key head 64, value head 128; one call's
# forward and dq + dk/dv, ms; 256x256 sub-tiles):
#   tile        causal                  window 512
#   512x1024    14.87 + 36.13 = 50.99   2.48 + 4.72 =  7.20
#   1024x1024   12.28 + 31.85 = 44.13   2.32 + 4.58 =  6.90
#   512x2048    14.76 + 32.86 = 47.62
#   1024x512    22.08 + 35.92 = 58.00   2.94 + 5.07 =  8.01
#   512x512     23.62 + 41.69 = 65.30   3.09 + 5.65 =  8.73
#   256x1024    20.98 + 43.84 = 64.82
#   256x512                             3.83 + 7.16 = 10.98
#   512x256                             4.47 + 8.06 = 12.53
#   256x256                             5.18 + 9.54 = 14.72
# 1024x2048 does not fit VMEM (the dq kernel). At 1024x1024 the
# sub-tile hardly matters (causal: 256x256 44.13, 512x512 44.13,
# 128x128 44.53; windowed: 256x256 6.90, 128x128 7.10, 512x512 7.21).
# So a head of 64 or less takes a q tile of 1024 where the sequence has
# one (_HEAD_DIM_SMALL): half the bytes a row, so twice the rows in the
# same VMEM. The causal call then runs at 51% of the MXU's peak on the
# scores it needs. A windowed call wants the same tile, not a shorter
# one that hugs its band: it is paced by its grid steps (2.4 us each,
# three a q tile), not by the 1.4 ms of arithmetic the band needs, and
# longer tiles are fewer steps.
#
# Measured at D=256 over grouped heads on v5e silicon (PR 33: B1, 16
# query heads over 2 key-value heads, S16384, the gated attention of
# models/qwen3next.py; one call's forward, and forward + backward less
# that forward, by the host's clock, ms; 256x256 sub-tiles):
#   512x1024   16.24 + 48.38 =  64.62
#   1024x512   16.07 + 50.84 =  66.91
#   256x1024   18.77 + 50.82 =  69.60
#   512x512    17.96 + 54.40 =  72.37
#   256x512    23.65 + 60.03 =  83.68
#   512x2048   16.12 + 135.87 = 151.99
# 1024x1024 does not fit VMEM (the dk/dv kernel, which holds a group's
# eight query heads' worth of accumulators). The default pair holds for
# grouped heads at 256 as it does for equal ones: no rung changed, and
# in the cell's step the three kernels run at 74% of their roofline.
#
# Measured at D=64 over grouped heads on v5e silicon (PR 39: B4, 32
# query heads over 8 key-value heads, S8192, the attention of
# models/lfm2.py; one call's forward, and forward + backward less that
# forward, by the host's clock, ms; 256x256 sub-tiles):
#   1024x1024  21.10 + 53.14 =  74.24   (the ladder's own pick: 74.23)
#   512x2048   23.37 + 52.27 =  75.64
#   512x1024   24.19 + 58.60 =  82.79
#   2048x512   30.55 + 58.15 =  88.70
#   1024x512   36.93 + 61.86 =  98.79
#   256x1024   31.56 + 68.93 = 100.50
#   512x512    38.76 + 69.64 = 108.40
# 2048x1024 (the forward) and 1024x2048 (the dq kernel) do not fit
# VMEM. The q tile of 1024 that PR 31 gave a head of 64 holds at four
# query heads a key-value head and half the sequence: no rung changed.
#
# Measured at a score head of 192 over a value head of 128 on v5e
# silicon (PR 41: B1, 32 heads, S16384, the latent attention of
# models/ling3flash.py, the kernels' first D != Dv shape; one call's
# forward, and forward + backward less that forward, by the host's
# clock, ms; 256x256 sub-tiles):
#   512x1024   34.85 +  87.68 = 122.53   (the ladder's own pick; again
#                                         with the tiles named: 122.41)
#   512x512    48.43 +  98.29 = 146.72
#   256x1024   47.64 + 102.37 = 150.01
# 1024x1024 does not fit VMEM (the dk/dv kernel). The default pair holds
# at 192 / 128: no rung changed; 12.6 TFLOP of needed scores in 122.5 ms
# is 52% of the chip's peak.
#
# Measured at D=128 under a window with whole tiles between its two
# diagonals on v5e silicon (PR 49: B2, 28 query heads over 4 key-value
# heads, S16384, a window of 4096, the windowed layers of
# models/smallthinker.py; one call's forward, and forward + backward
# less that forward, by the host's clock, ms; 256x256 sub-tiles):
#   1024x1024  15.81 + 40.70 =  56.51
#   512x1024   17.25 + 43.97 =  61.21   (the causal ladder's pick)
#   1024x512   27.19 + 47.10 =  74.29
#   512x2048   23.40 + 53.85 =  77.25
#   512x512    27.17 + 50.13 =  77.30
#   256x1024   22.18 + 55.47 =  77.65
#   256x2048   24.95 + 57.78 =  82.73
#   2048x512   29.84 + 61.19 =  91.03
#   256x512    31.95 + 64.92 =  96.87
# 1024x2048 does not fit VMEM (the forward). The same heads' causal
# call at 512x1024 reads 32.89 + 92.82 = 125.72 (512x512 165.75,
# 1024x512 156.26). A band eight key tiles of 512 deep is paced by its
# arithmetic (40.05 TFLOP of needed scores a step run at 66% of the
# MXU's peak in the cell), and still wants the longer q tile: 7.7% less
# at 1024 rows, where a group of seven's accumulators fit at a head of
# 128. So a windowed call takes a q tile of 1024 up to D=128
# (_HEAD_DIM_WINDOW). The same heads' causal call reads 29.93 + 86.31 =
# 116.24 at 1024x1024 (7.5% under its 512x1024; 256x1024 146.81), but
# the causal ladder at D=128 is three other cells' too, at equal heads
# and shorter rows, where 1024x1024 was never read: it stays.
_BLOCK_Q_LADDER = (512, 256, 128)
_BLOCK_K_LADDER = (1024, 512, 256, 128)
_HEAD_DIM_BASE = 256  # the largest D the default ladder was measured at
_HEAD_DIM_SMALL = 64  # up to here a q tile of 1024 fits, and is faster
_HEAD_DIM_WINDOW = 128  # the same for a call with a window


def _ladders_for(head_dim: int, window=None):
    """(q_ladder, k_ladder) scaled to ``head_dim``: the measured
    512x1024 defaults up to D=256 (a q tile of 1024 on top up to D=64,
    and under a ``window`` up to D=128), then each doubling of D halves
    the leading tiles (floor 128) so per-program VMEM stays level."""
    q_top, k_top = _BLOCK_Q_LADDER[0], _BLOCK_K_LADDER[0]
    d = max(1, int(head_dim))
    while d > _HEAD_DIM_BASE and (q_top > 128 or k_top > 128):
        q_top = max(128, q_top // 2)
        k_top = max(128, k_top // 2)
        d //= 2
    q_ladder = tuple(b for b in _BLOCK_Q_LADDER if b <= q_top)
    k_ladder = tuple(b for b in _BLOCK_K_LADDER if b <= k_top)
    if head_dim <= (_HEAD_DIM_SMALL if window is None else _HEAD_DIM_WINDOW):
        q_ladder = (2 * q_ladder[0],) + q_ladder
    return q_ladder, k_ladder


# Causal sub-tile (q rows, kv columns) by head size, as (largest head
# size, sub-tile) rungs: the DMA tile and the grid stay as large as the
# ladders above measured them, and inside a tile the diagonal crosses
# the kernels compute by sub-tile (_diagonal_blocks). Measured on v5e
# silicon (PR 30; 512x1024 tiles, forward + dq + dk/dv of one layer,
# ms; "whole" is the whole-tile predicate the kernels had):
#   sub-tile   BH64 S2048 D128         BH80 S4096 D256
#   whole      1.162+1.219+1.480=3.861  8.172+9.640+10.808=28.620
#   512x512    1.054+1.082+1.251=3.387  7.122+8.978+ 9.925=26.025
#   256x512    1.088+1.075+1.292=3.455  7.260+8.879+ 9.942=26.080
#   256x256    1.080+1.038+1.239=3.357  7.134+8.626+ 9.517=25.276
#   128x128    1.142+1.035+1.348=3.526  7.546+8.545+ 9.498=25.589
#    64x256    1.302+1.148+2.215=4.665  7.943+8.760+11.085=27.788
# 256x256 computes 56.25% of the square at S=2048 and 53.1% at S=4096
# (whole: 75% and 62.5%). A row chunk is one body over all the columns
# it sees, masked in its last sub-tile only: the same sub-tiles as a
# loop over them with runtime trip counts ran 64% and 31% SLOWER than
# whole (the forward's statistics and accumulator carried through the
# loop), and one softmax update per sub-tile only 3% and 10% faster
# (the per-row work of an update, reductions across lanes above all,
# is paid again by every block of a row). One rung: both head sizes
# want the same, and so does 64 (PR 31: the table beside the tile
# ladders); past 256 nothing is measured and the last rung stands.
_SUBTILE_LADDER = ((256, (256, 256)),)


def _subtile_for(head_dim: int, block_q: int, block_k: int):
    """The causal sub-tile for a (block_q, block_k) tile at
    ``head_dim``: the rung of the smallest head size that holds it (the
    last one past that), cut to the tile; a tile it does not divide is
    its own sub-tile."""
    sub_q, sub_k = next(
        (sub for d, sub in _SUBTILE_LADDER if head_dim <= d),
        _SUBTILE_LADDER[-1][1])
    sub_q, sub_k = min(sub_q, block_q), min(sub_k, block_k)
    return (sub_q if block_q % sub_q == 0 else block_q,
            sub_k if block_k % sub_k == 0 else block_k)


def _note_subtiles(seq_q, seq_k, head_dim, block_q, block_k, q_offset,
                   k_offset, window=None) -> None:
    """Write ``causal_subtile_counts`` of the call being traced into the
    metrics registry (``hvd_flash_subtiles{kind=...}``, and with a
    window ``{kind=...,window="<n>"}``: docs/metrics.md), where a world
    with its metrics plane on is there to read it. Traced offsets (the
    ring's) have no count at trace time and write nothing."""
    from horovod_tpu.common import basics
    try:
        q_offset, k_offset = int(q_offset), int(k_offset)
    except TypeError:       # a tracer: jax.errors.ConcretizationTypeError
        return
    basics.note_traced(
        "hvd_flash_subtiles",
        "sub-tiles a head of the causal flash call traced last: "
        "computed, of those masked, and skipped",
        causal_subtile_counts(
            seq_q, seq_k, block_q, block_k,
            _subtile_for(head_dim, block_q, block_k), q_offset, k_offset,
            window),
        "" if window is None else f',window="{window}"')


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


def _run(q, k, v, offsets, causal, block_q, block_k, interpret,
         window=None, out_dtype=None):
    b, seq_q, h, d = q.shape
    o, m, l = _flash_bhsd(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v), offsets,
                          causal, block_q, block_k, bool(interpret),
                          window=window, out_dtype=out_dtype)
    o = _from_bhsd(o, b, h)
    m = m[:, 0].reshape(b, h, seq_q)
    l = l[:, 0].reshape(b, h, seq_q)
    return o, m, l


def _check_call(q, k, v, causal, window):
    """The shapes a call may have: [B, S, H, D] with k and v over a
    divisor of q's heads, v's head size its own; a window only under
    the causal mask."""
    if k.shape[:3] != v.shape[:3] or k.shape[-1] != q.shape[-1] \
            or q.shape[2] % k.shape[2]:
        raise ValueError(
            f"q{q.shape} k{k.shape} v{v.shape}: k and v share batch, "
            f"length and heads, k has q's head size, and q's heads are a "
            f"multiple of theirs")
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window {window!r} needs causal=True and at "
                         f"least the row's own key")


def _blocks_for(q, k, block_q, block_k, window=None):
    """The (q, kv) tile of a call: explicit, or the largest rung of the
    head size's ladder (a windowed call's, under a ``window``) that
    divides the sequence."""
    q_ladder, k_ladder = _ladders_for(q.shape[-1], window)
    return (_auto_block(q.shape[1], q_ladder, block_q),
            _auto_block(k.shape[1], k_ladder, block_k))


def flash_attention_stats(q, k, v, causal: bool = True,
                          q_offset=0, k_offset=0,
                          block_q: Optional[int] = None,
                          block_k: Optional[int] = None,
                          interpret: Optional[bool] = None,
                          window: Optional[int] = None
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Forward-only flash attention that also returns the softmax
    statistics: (o [B,Sq,H,Dv], m [B,H,Sq] running max, l [B,H,Sq]
    normalizer). Ring attention merges these across rotated kv shards.
    Offsets may be traced values (one compilation serves every ring
    step)."""
    _check_call(q, k, v, causal, window)
    seq_q, seq_k = q.shape[1], k.shape[1]
    block_q, block_k = _blocks_for(q, k, block_q, block_k, window)
    if not _shapes_ok(seq_q, seq_k, block_q, block_k):
        raise ValueError(
            f"sequence lengths ({seq_q}, {seq_k}) must be divisible by "
            f"blocks ({block_q}, {block_k})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if causal:
        _note_subtiles(seq_q, seq_k, q.shape[-1], block_q, block_k,
                       q_offset, k_offset, window)
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32)])
    return _run(q, k, v, offsets, causal, block_q, block_k, interpret,
                window)


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
                        interpret: Optional[bool] = None,
                        window: Optional[int] = None):
    """Raw flash backward against externally-merged softmax stats.

    q: [B,Sq,H,D]; k: [B,Sk,Hkv,D]; v: [B,Sk,Hkv,Dv]; o, do:
    [B,Sq,H,Dv]; m, l: [B,H,Sq] (as returned — or ring-merged — from
    flash_attention_stats). Returns (dq, dk, dv) in the input dtypes,
    dk and dv summed over the query heads that share their head. Ring
    attention calls this once per rotated kv shard with the *global*
    lse, which makes per-shard contributions sum to the exact
    full-sequence gradient."""
    _check_call(q, k, v, causal, window)
    b, seq_q, h, d = q.shape
    seq_k = k.shape[1]
    block_q, block_k = _blocks_for(q, k, block_q, block_k, window)
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
                                 bool(interpret), window=window)
    return (_from_bhsd(dq, b, h), _from_bhsd(dk, b, k.shape[2]),
            _from_bhsd(dv, b, k.shape[2]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, offsets, causal, block_q, block_k, interpret,
           window=None, out_dtype=None):
    return _run(q, k, v, offsets, causal, block_q, block_k, interpret,
                window, out_dtype)[0]


def _flash_fwd(q, k, v, offsets, causal, block_q, block_k, interpret,
               window, out_dtype):
    o, m, l = _run(q, k, v, offsets, causal, block_q, block_k, interpret,
                   window, out_dtype)
    return o, (q, k, v, o, m, l, offsets)


def _flash_bwd(causal, block_q, block_k, interpret, window, out_dtype,
               residuals, g):
    import numpy as np
    q, k, v, o, m, l, offsets = residuals
    dq, dk, dv = flash_attention_bwd(
        q, k, v, o, m, l, g, causal=causal,
        q_offset=offsets[0], k_offset=offsets[1],
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window)
    d_offsets = np.zeros(offsets.shape, jax.dtypes.float0)
    return dq, dk, dv, d_offsets


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    q_offset=0, k_offset=0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None, out_dtype=None):
    """Blockwise-softmax attention. q: [B, Sq, H, D] (the module layout
    of models/transformer.py); k: [B, Sk, Hkv, D]; v: [B, Sk, Hkv, Dv];
    returns [B, Sq, H, Dv] in q.dtype, or in ``out_dtype``: the kernel
    writes from a float32 accumulator, and a caller that subtracts two
    nearly equal maps (differential attention) wants it unrounded.

    **Fewer key-value heads**: ``Hkv`` divides ``H``, and query head
    ``h`` reads key-value head ``h // (H / Hkv)`` (consecutive query
    heads share one). Nothing is repeated in HBM: the kernels' index
    maps send a group's query heads to the same k and v tiles, and the
    dk/dv kernel sums over the group in its grid.

    **A value head of its own size**: ``Dv`` need not be ``D``; the
    scores scale by ``D ** -0.5`` and the output has ``Dv``.

    **``window``** (with ``causal``): a row sees its own key and the
    ``window - 1`` before it. Tiles wholly behind the window are
    skipped in all three kernels as those above the diagonal are, and
    not fetched: each kernel's grid walks only the tiles the band can
    touch (``_streamed_tiles``). ``None`` is the causal mask alone,
    which lowers to what it did before the window existed.

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
    _check_call(q, k, v, causal, window)
    seq_q, seq_k = q.shape[1], k.shape[1]
    bq, bk = _blocks_for(q, k, block_q, block_k, window)
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
        return _dense_reference(q, k, v, causal, q_offset, k_offset,
                                window, out_dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if causal:
        _note_subtiles(seq_q, seq_k, q.shape[-1], bq, bk, q_offset,
                       k_offset, window)
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32)])
    return _flash(q, k, v, offsets, bool(causal), bq, bk,
                  bool(interpret), window,
                  None if out_dtype is None else jnp.dtype(out_dtype))
