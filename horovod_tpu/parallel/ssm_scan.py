"""Pallas selective scan: the recurrence of a state-space layer
(Mamba, arXiv:2312.00752) as TPU kernels, forward and backward.

For every channel ``d`` and state ``n`` (``A`` negative)::

    h_t[d, n] = exp(delta_t[d] A[d, n]) h_{t-1}[d, n]
                + delta_t[d] u_t[d] B_t[n]
    y_t[d]    = sum_n h_t[d, n] C_t[n] + D[d] u_t[d]

The recurrence is sequential in ``t`` and elementwise in ``(d, n)``,
with a decay of its own for every pair: no matmul form exists, so it
runs on the vector unit. What the kernels avoid is the other two ways
to write it: a ``lax.scan`` over the sequence (one tiny XLA step a
position) and an associative scan, which materialises ``[S, D, N]``
in HBM (1.3 GB a layer in float32 at S 16384, D 5120, N 16).

Kernel shape:

- **channels fill the vector registers, time is a loop.** A block of
  1024 channels is one [8, 128] register; the wrapper lays ``u``,
  ``delta`` and ``y`` out as ``[B, S, D / 1024, 8, 128]`` so that step
  ``t``'s channels are one aligned register read from a leading index.
  The state of a block is ``N`` such registers, carried through the
  loop; ``B_t[n]`` and ``C_t[n]`` are scalars read from SMEM, so every
  operation of a step is a full-register multiply, add or exponential
  with a scalar or register operand: no broadcast across lanes, no
  reduction, in the forward kernel.
- grid ``(batch, channel blocks, chunks)``, the chunk innermost: the
  state lives in float32 VMEM scratch between the chunks of a
  sequence, zero at the first.
- ``ssm_scan_fwd`` also writes the state that **entered** each chunk
  (``[B, chunks, D / 1024, N, 8, 128]``: 42 MB a layer at the cell's
  shape and a chunk of 128). ``ssm_scan_bwd`` walks the chunks from
  the last to the first: it recomputes a chunk's states from the one
  that entered it into VMEM (never HBM), then runs the reverse
  recurrence ``G_t = dy_t C_t + a_{t+1} G_{t+1}`` over the chunk and
  writes ``du``, ``ddelta`` and, per channel block, the partial
  ``dB_t``, ``dC_t`` (sums over the block's channels: the backward's
  only reductions), ``dA`` and ``dD`` (sums over time, accumulated in
  the output block).

``selective_scan`` is differentiable through the two kernels
(``custom_vjp``); ``selective_scan_reference`` is the literal
recurrence, the tests' oracle. Off the TPU the kernels run in
interpreter mode, as the flash kernels do.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

# Chunk length by sequence length, as (longest sequence, chunk) rungs;
# the last rung stands past it. The chunk bounds the backward's VMEM
# (it keeps chunk + 1 states of a channel block: 64 KB a step at N 16)
# and sets how many entering states the forward writes. Measured on
# v5e silicon (PR 31; B1 S16384 D5120 N16, one layer; the forward
# kernel, the backward kernel, and forward + backward through
# ``selective_scan`` with the relayouts around the kernels, ms):
#   chunk   fwd     bwd      whole
#    32     3.632   17.127   26.741
#    64     3.472   16.915   26.307
#   128     3.380   16.774   26.106
#   256     3.367   16.746   26.059
#   512     3.359   16.851   26.107
# Flat within 3%: a chunk's entering state is 0.3 MB against 5 MB of
# operands, and the kernels are paced by their vector operations (the
# backward by its 32 sums over a register a position), not by what a
# chunk moves. One rung, the smallest within 0.2% of the best: nothing
# is measured at another shape.
_CHUNK_LADDER = ((None, 128),)


def _chunk_for(seq: int) -> int:
    """The ladder's chunk for a sequence of ``seq``, no longer than the
    sequence rounded up to 8 positions."""
    chunk = next(c for longest, c in _CHUNK_LADDER
                 if longest is None or seq <= longest)
    return min(chunk, max(8, -(-seq // 8) * 8))


def _channel_tile(channels: int):
    """``(blocks, rows, lanes)`` with blocks x rows x lanes =
    ``channels``: [8, 128] registers where the width allows it (the
    chip's case), one narrower tile otherwise (the tests')."""
    lanes = 128 if channels % 128 == 0 else channels
    rows_all = channels // lanes
    rows = 8 if rows_all % 8 == 0 else rows_all
    return rows_all // rows, rows, lanes


def _note_chunks(seq: int, chunk: int) -> None:
    """``hvd_ssm_scan_chunks{kind=...}`` of the call being traced
    (docs/metrics.md)."""
    from horovod_tpu.common import basics
    basics.note_traced(
        "hvd_ssm_scan_chunks",
        "the selective scan traced last: chunks a sequence and "
        "positions a chunk",
        {"chunks": -(-seq // chunk), "chunk_length": chunk})


def _next_states(bc_ref, t, dl, du, a_all, hs):
    """``h_t`` of every state from ``h_{t-1}``: the recurrence's one
    line, ``exp(delta A) h + delta u B_t``."""
    states = len(hs)
    return [jnp.exp(dl * a_all[n]) * hs[n]
            + du * bc_ref[0, 0, t * 2 * states + n] for n in range(states)]


def _fwd_kernel(bc_ref, u_ref, dl_ref, a_ref, d_ref, y_ref, hent_ref,
                h_scr, *, chunk: int, states: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[:] = jnp.zeros_like(h_scr)

    hent_ref[0, 0, 0] = h_scr[:]
    a_all = [a_ref[0, n] for n in range(states)]
    skip = d_ref[0]

    def step(t, hs):
        dl, ut = dl_ref[0, t, 0], u_ref[0, t, 0]
        hs = _next_states(bc_ref, t, dl, dl * ut, a_all, hs)
        y = skip * ut
        for n in range(states):
            y = y + hs[n] * bc_ref[0, 0, t * 2 * states + states + n]
        y_ref[0, t, 0] = y
        return tuple(hs)

    hs = jax.lax.fori_loop(
        0, chunk, step, tuple(h_scr[n] for n in range(states)))
    for n in range(states):
        h_scr[n] = hs[n]


def _bwd_kernel(bc_ref, u_ref, dl_ref, a_ref, d_ref, hent_ref, dy_ref,
                du_ref, ddl_ref, dbc_ref, da_ref, dd_ref, k_scr, h_buf, *,
                chunk: int, states: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)       # the sequence's last chunk
    def _():
        k_scr[:] = jnp.zeros_like(k_scr)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    a_all = [a_ref[0, n] for n in range(states)]
    skip = d_ref[0]

    # the chunk's states again, from the one that entered it:
    # h_buf[t + 1] = h_t, h_buf[0] the entering state
    h_buf[0] = hent_ref[0, 0, 0]

    def again(t, hs):
        dl = dl_ref[0, t, 0]
        hs = _next_states(bc_ref, t, dl, dl * u_ref[0, t, 0], a_all, hs)
        for n in range(states):
            h_buf[t + 1, n] = hs[n]
        return tuple(hs)

    jax.lax.fori_loop(0, chunk, again,
                      tuple(h_buf[0, n] for n in range(states)))

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * states), 1)

    def total(x):
        """The sum of a register, as [1, 1]."""
        return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1,
                       keepdims=True)

    def back(i, carry):
        ks, dd = carry
        t = chunk - 1 - i
        dl, ut, dy = dl_ref[0, t, 0], u_ref[0, t, 0], dy_ref[0, t, 0]
        du = dl * ut
        into_b = jnp.zeros_like(dl)     # sum_n G B_t[n]
        into_a = jnp.zeros_like(dl)     # sum_n G h_{t-1} a A[n]
        row = jnp.zeros((1, 2 * states), jnp.float32)
        out = []
        for n in range(states):
            a = jnp.exp(dl * a_all[n])
            g = dy * bc_ref[0, 0, t * 2 * states + states + n] + ks[n]
            row = jnp.where(lane == n, total(g * du), row)
            row = jnp.where(lane == states + n,
                            total(dy * h_buf[t + 1, n]), row)
            into_b = into_b + g * bc_ref[0, 0, t * 2 * states + n]
            w = g * h_buf[t, n] * a
            into_a = into_a + w * a_all[n]
            da_ref[0, 0, n] += w * dl
            out.append(g * a)
        ddl_ref[0, t, 0] = into_b * ut + into_a
        du_ref[0, t, 0] = into_b * dl + skip * dy
        dbc_ref[0, 0, pl.ds(t, 1), :] = row
        return tuple(out), dd + dy * ut

    ks, dd = jax.lax.fori_loop(
        0, chunk, back,
        (tuple(k_scr[n] for n in range(states)), jnp.zeros_like(skip)))
    for n in range(states):
        k_scr[n] = ks[n]
    dd_ref[0, 0] += dd


def _compiler_params(chunk: int, states: int, rows: int, lanes: int):
    """The grid's semantics and a VMEM limit for a kernel that keeps
    ``chunk`` + 1 states of a channel block in scratch (the backward;
    0 for the forward), beside double-buffered blocks of five
    [chunk, rows, lanes] arrays."""
    from jax.experimental.pallas import tpu as pltpu
    tile = rows * lanes * 4
    need = (chunk + 1) * states * tile + 12 * chunk * tile \
        + 8 * states * tile + 4 * chunk * 128 * 4
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=int(min(100 << 20, max(32 << 20, 2 * need))))


def _laid_out(u, delta, a, b, c, d, chunk):
    """The kernels' operands from the module's: time padded to whole
    chunks (a padded step has delta 0: the state passes through it
    unchanged), channels as [blocks, rows, lanes] registers, ``B`` and
    ``C`` side by side a chunk a row for SMEM."""
    bt, seq, channels = u.shape
    states = a.shape[1]
    blocks, rows, lanes = _channel_tile(channels)
    n_chunks = -(-seq // chunk)
    pad = n_chunks * chunk - seq

    def timed(x):
        x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
        return x.reshape(bt, n_chunks * chunk, blocks, rows, lanes)

    bc = jnp.pad(jnp.concatenate([b, c], -1).astype(jnp.float32),
                 ((0, 0), (0, pad), (0, 0)))
    return (bc.reshape(bt * n_chunks, 1, chunk * 2 * states), timed(u),
            timed(delta),
            a.astype(jnp.float32).T.reshape(states, blocks, rows, lanes)
            .transpose(1, 0, 2, 3),
            d.astype(jnp.float32).reshape(blocks, rows, lanes))


def _specs(chunk, states, n_chunks, rows, lanes, reverse: bool):
    """Block specs over the grid (batch, channel block, chunk); with
    ``reverse`` the chunks are walked from the last to the first."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    at = (lambda c: n_chunks - 1 - c) if reverse else (lambda c: c)
    return dict(
        bc=pl.BlockSpec((1, 1, chunk * 2 * states),
                        lambda b, g, c: (b * n_chunks + at(c), 0, 0),
                        memory_space=pltpu.SMEM),
        timed=pl.BlockSpec((1, chunk, 1, rows, lanes),
                           lambda b, g, c: (b, at(c), g, 0, 0)),
        a=pl.BlockSpec((1, states, rows, lanes),
                       lambda b, g, c: (g, 0, 0, 0)),
        d=pl.BlockSpec((1, rows, lanes), lambda b, g, c: (g, 0, 0)),
        hent=pl.BlockSpec((1, 1, 1, states, rows, lanes),
                          lambda b, g, c: (b, at(c), g, 0, 0, 0)))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _scan_fwd(bc, u, delta, a, d, chunk: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, padded, blocks, rows, lanes = u.shape
    states, n_chunks = a.shape[1], padded // chunk
    s = _specs(chunk, states, n_chunks, rows, lanes, reverse=False)
    elements = bt * padded * blocks * rows * lanes * states
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, states=states),
        grid=(bt, blocks, n_chunks),
        in_specs=[s["bc"], s["timed"], s["timed"], s["a"], s["d"]],
        out_specs=(s["timed"], s["hent"]),
        out_shape=(
            jax.ShapeDtypeStruct(u.shape, jnp.float32),
            jax.ShapeDtypeStruct(
                (bt, n_chunks, blocks, states, rows, lanes), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((states, rows, lanes), jnp.float32)],
        compiler_params=_compiler_params(0, states, rows, lanes),
        interpret=interpret,
        name="ssm_scan_fwd",
        cost_estimate=pl.CostEstimate(
            flops=6 * elements, transcendentals=elements,
            bytes_accessed=4 * (3 * u.size + bc.size)
            + 4 * bt * n_chunks * blocks * states * rows * lanes),
    )(bc, u, delta, a, d)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _scan_bwd(bc, u, delta, a, d, hent, dy, chunk: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, padded, blocks, rows, lanes = u.shape
    states, n_chunks = a.shape[1], padded // chunk
    s = _specs(chunk, states, n_chunks, rows, lanes, reverse=True)
    elements = bt * padded * blocks * rows * lanes * states
    dbc = pl.BlockSpec(
        (1, 1, chunk, 2 * states),
        lambda b, g, c: (b, g, n_chunks - 1 - c, 0))
    da = pl.BlockSpec((1, 1, states, rows, lanes),
                      lambda b, g, c: (b, g, 0, 0, 0))
    dd = pl.BlockSpec((1, 1, rows, lanes), lambda b, g, c: (b, g, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, states=states),
        grid=(bt, blocks, n_chunks),
        in_specs=[s["bc"], s["timed"], s["timed"], s["a"], s["d"],
                  s["hent"], s["timed"]],
        out_specs=(s["timed"], s["timed"], dbc, da, dd),
        out_shape=(
            jax.ShapeDtypeStruct(u.shape, jnp.float32),
            jax.ShapeDtypeStruct(u.shape, jnp.float32),
            jax.ShapeDtypeStruct((bt, blocks, padded, 2 * states),
                                 jnp.float32),
            jax.ShapeDtypeStruct((bt, blocks, states, rows, lanes),
                                 jnp.float32),
            jax.ShapeDtypeStruct((bt, blocks, rows, lanes), jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((states, rows, lanes), jnp.float32),
            pltpu.VMEM((chunk + 1, states, rows, lanes), jnp.float32)],
        compiler_params=_compiler_params(chunk, states, rows, lanes),
        interpret=interpret,
        name="ssm_scan_bwd",
        cost_estimate=pl.CostEstimate(
            flops=20 * elements, transcendentals=2 * elements,
            bytes_accessed=4 * (5 * u.size + bc.size + hent.size)),
    )(bc, u, delta, a, d, hent, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(u, delta, a, b, c, d, chunk, interpret):
    return _scan_vjp_fwd(u, delta, a, b, c, d, chunk, interpret)[0]


def _scan_vjp_fwd(u, delta, a, b, c, d, chunk, interpret):
    bt, seq, channels = u.shape
    ops = _laid_out(u, delta, a, b, c, d, chunk)
    y, hent = _scan_fwd(*ops, chunk=chunk, interpret=interpret)
    y = y.reshape(bt, -1, channels)[:, :seq].astype(u.dtype)
    return y, (u, delta, a, b, c, d, hent)


def _scan_vjp_bwd(chunk, interpret, res, dy):
    u, delta, a, b, c, d, hent = res
    bt, seq, channels = u.shape
    states = a.shape[1]
    ops = _laid_out(u, delta, a, b, c, d, chunk)
    padded = ops[1].shape[1]
    dy = jnp.pad(dy.astype(jnp.float32),
                 ((0, 0), (0, padded - seq), (0, 0))).reshape(ops[1].shape)
    du, ddl, dbc, da, dd = _scan_bwd(*ops, hent, dy, chunk=chunk,
                                     interpret=interpret)
    timed = lambda x, like: x.reshape(bt, padded, channels)[:, :seq] \
        .astype(like.dtype)
    dbc = jnp.sum(dbc, axis=1)[:, :seq]           # over channel blocks
    da = jnp.sum(da, axis=0).transpose(1, 0, 2, 3).reshape(
        states, channels).T
    return (timed(du, u), timed(ddl, delta), da.astype(a.dtype),
            dbc[..., :states].astype(b.dtype),
            dbc[..., states:].astype(c.dtype),
            jnp.sum(dd, axis=0).reshape(channels).astype(d.dtype))


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def selective_scan(u, delta, a, b, c, d, chunk: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """``y`` [B, S, D] in ``u.dtype`` of the recurrence in the module
    docstring. u, delta: [B, S, D] (``delta`` positive, already through
    its softplus); a: [D, N], negative; b, c: [B, S, N]; d: [D]. The
    state and every product are float32 whatever the operands' types.
    ``chunk`` None takes the ladder's (``_CHUNK_LADDER``); a length
    that is no multiple of it is padded with steps that leave the state
    as it is. Differentiable in all six operands."""
    if delta.shape != u.shape or b.shape != c.shape \
            or b.shape[:2] != u.shape[:2] or a.shape[0] != u.shape[2] \
            or a.shape[1] != b.shape[2] or d.shape != (u.shape[2],):
        raise ValueError(
            f"u{u.shape} delta{delta.shape} a{a.shape} b{b.shape} "
            f"c{c.shape} d{d.shape}: want [B,S,D], [B,S,D], [D,N], "
            f"[B,S,N], [B,S,N], [D]")
    chunk = _chunk_for(u.shape[1]) if chunk is None else int(chunk)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _note_chunks(u.shape[1], chunk)
    return _scan(u, delta, a, b, c, d, chunk, bool(interpret))


def selective_scan_reference(u, delta, a, b, c, d):
    """The literal recurrence, one ``lax.scan`` step a position, in
    float32: the kernels' oracle."""
    u, delta, b, c = (x.astype(jnp.float32) for x in (u, delta, b, c))

    def step(h, xs):
        ut, dl, bt, ct = xs                       # [B,D] [B,D] [B,N] [B,N]
        h = jnp.exp(dl[..., None] * a) * h \
            + (dl * ut)[..., None] * bt[:, None, :]
        return h, jnp.sum(h * ct[:, None, :], -1) + d * ut

    h0 = jnp.zeros((u.shape[0],) + a.shape, jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(
        x.transpose(1, 0, 2) for x in (u, delta, b, c)))
    return y.transpose(1, 0, 2)
