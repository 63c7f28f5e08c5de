"""The Kimi delta attention rule's kernels
(``horovod_tpu/parallel/kda.py``: a delta rule with a decay for every
key channel) in interpreter mode against the literal recurrence:
forward and every gradient (q, k, v, g, beta), at lengths that are and
are not a multiple of the chunk, with one level of sub-blocks and with
a tree of them; a gate at its published bound for whole sub-blocks
stays finite; the scalar rule is the special case of equal channels;
the triangular inverse by blocks is exact where doublings over the
chunk are not, and keys that resemble one another under a slow decay
come out right at a chunk of 128. (Cold on this sandbox: 35 s.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import gated_delta as gd
from horovod_tpu.parallel import kda

from .compiled import out_and_vjp
from .test_gated_delta import kernel_products

pytestmark = [pytest.mark.fast, pytest.mark.time_limit(120),
              pytest.mark.interpreter_of_its_own]


def operands(seed, batch, seq, heads, dk, dv, lower=-1.0, shift=0.0,
             dtype=jnp.float32):
    """q, k as the layer hands them over (L2-normalised, q scaled), v,
    a log-decay a key channel in (``lower``, 0) and beta in (0, 1)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (batch, seq, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, seq, heads, dk)))
    v = jax.random.normal(ks[2], (batch, seq, heads, dv))
    g = lower * jax.nn.sigmoid(
        jax.random.normal(ks[3], (batch, seq, heads, dk)) + shift)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


# (case, sequence, chunk, sub-block, heads, Dk, Dv, the gate's bound)
CASES = [("a_tree_of_two_levels", 64, 32, 8, 2, 16, 8, -1.0),
         ("a_ragged_tail", 40, 16, 8, 2, 16, 8, -1.0),
         ("the_diagonal_level_alone", 24, 8, 8, 2, 8, 16, -1.0),
         ("shorter_than_a_chunk", 11, 16, 8, 1, 8, 8, -1.0),
         ("the_published_bound_at_sub_blocks_of_16", 64, 64, 16, 1, 16, 16,
          -5.0)]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_the_kernels_are_the_recurrence_forward_and_backward(case):
    _, seq, chunk, sub, heads, dk, dv, lower = case
    args = operands(2, 2, seq, heads, dk, dv, lower)
    weight = jax.random.normal(jax.random.key(9), (2, seq, heads, dv))
    got, got_grads = out_and_vjp(
        lambda *a: kda.kimi_delta_attention(*a, chunk=chunk, sub=sub,
                                            interpret=True), weight, *args)
    with jax.default_matmul_precision("highest"):
        want, want_grads = out_and_vjp(kda.kda_reference, weight, *args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got_grads,
                          want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=2e-5 * float(jnp.abs(w).max()),
            err_msg=f"d{name}")


@pytest.mark.parametrize("case", CASES[:2], ids=[c[0] for c in CASES[:2]])
def test_the_forward_hands_the_backward_each_chunks_inverse(case):
    """``kda_fwd``'s third output is ``T = (I + diag(beta) M)^-1`` of
    every head's chunk: against ``_unit_lower_inverse_by_blocks`` of the
    same ``A`` made outside the kernel, ``M`` by the definition (a sum
    over channels of ``k_i k_j exp(G_i - G_j)``). The backward kernel
    reads it and takes no inverse: its products at full precision are
    ``G``'s running sum, the diagonal level's three, ``dA = T^T dT
    T^T``'s two and ``dg``'s, where the forward's are the running sum,
    the diagonal level's one and the inverse's."""
    _, seq, chunk, sub, heads, dk, dv, lower = case
    args = operands(2, 2, seq, heads, dk, dv, lower)
    ops = kda._laid_out(*args, chunk)
    static = dict(chunk=chunk, sub=sub, heads=heads, interpret=True)
    out, sent, t = kda._kda_fwd(*ops, **static)
    n_chunks = -(-seq // chunk)
    assert t.shape == (2, heads, n_chunks, chunk, chunk) \
        and t.dtype == jnp.float32 and sent.shape[:3] == t.shape[:3]

    @jax.jit
    def outside(k, g, beta):
        chunks = lambda x: x.reshape(2, n_chunks, chunk, heads, dk).transpose(
            0, 3, 1, 2, 4)
        k, g_sum = chunks(k), jnp.cumsum(chunks(g), axis=3)
        m = jnp.sum(k[..., :, None, :] * k[..., None, :, :] * jnp.exp(
            jnp.minimum(g_sum[..., :, None, :] - g_sum[..., None, :, :],
                        0.0)), axis=-1)
        a = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool), -1),
                      -beta[..., :, None] * m, 0.0)
        return jax.lax.map(
            lambda a: kda._unit_lower_inverse_by_blocks(a, kda._SOLVE_BLOCK),
            a.reshape(-1, chunk, chunk)).reshape(a.shape)

    np.testing.assert_allclose(t, outside(ops[1], ops[3], ops[4]),
                               rtol=1e-5, atol=1e-6)
    fwd = kernel_products(lambda *o: kda._kda_fwd(*o, **static), "kda_fwd",
                          *ops)
    bwd = kernel_products(lambda *o: kda._kda_bwd(*o, **static), "kda_bwd",
                          *ops, sent, t, out)
    assert bwd[1] == 7 and fwd[1] > 2, (fwd, bwd)
    assert gd.kept_bytes(chunk, dk, dv) \
        == 4 * (sent[0, 0, 0].size + t[0, 0, 0].size)


def test_a_gate_at_its_bound_for_whole_sub_blocks_stays_finite():
    """``g`` = -5 in every channel of every position: over a sub-block
    of 16 the diagonal level's growing factor reaches exp(75), inside
    float32; the state forgets within a position and the output is the
    position's own ``beta (q . k) v``."""
    q, k, v, g, beta = operands(4, 1, 64, 2, 16, 16)
    g = jnp.full_like(g, -5.0)
    weight = jnp.ones_like(v)
    got, grads = out_and_vjp(
        lambda *a: kda.kimi_delta_attention(*a, chunk=32, sub=16,
                                            interpret=True),
        weight, q, k, v, g, beta)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in (got, *grads))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(kda.kda_reference)(q, k, v, g, beta)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    own = (beta * jnp.sum(q * k, -1))[..., None] * v
    np.testing.assert_allclose(got, own, atol=0.02 * float(jnp.abs(own).max()))


def test_the_scalar_rule_is_the_special_case_of_equal_channels():
    """A decay that is the same in every key channel: the per-channel
    kernels, their oracle and the scalar rule's oracle agree, and the
    gradient of the shared decay is the sum over the channels."""
    q, k, v, g, beta = operands(5, 2, 48, 2, 16, 8)
    scalar = g[..., 0]
    weight = jax.random.normal(jax.random.key(6), v.shape)
    spread = lambda s: jnp.broadcast_to(s[..., None], q.shape)
    got, (_, _, _, got_dg, _) = out_and_vjp(
        lambda q, k, v, s, b: kda.kimi_delta_attention(
            q, k, v, spread(s), b, chunk=16, sub=8, interpret=True),
        weight, q, k, v, scalar, beta)
    with jax.default_matmul_precision("highest"):
        want, (_, _, _, want_dg, _) = out_and_vjp(
            gd.gated_delta_rule_reference, weight, q, k, v, scalar, beta)
        ours = jax.jit(kda.kda_reference)(q, k, v, spread(scalar), beta)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ours, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        got_dg, want_dg, rtol=1e-4, atol=2e-5 * float(jnp.abs(want_dg).max()))


def test_bfloat16_operands_keep_the_state_and_the_decay_in_float32():
    """The model's call: q, k, v in bfloat16, g in float32. The result
    is within a bfloat16's rounding of the float32 recurrence on the
    same rounded operands, and comes back in v's type."""
    args = operands(3, 1, 48, 2, 16, 16, dtype=jnp.bfloat16)
    got = jax.jit(lambda *a: kda.kimi_delta_attention(
        *a, chunk=16, sub=8, interpret=True))(*args)
    want = jax.jit(kda.kda_reference)(*args)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=0.05,
                               atol=0.02 * float(jnp.abs(want).max()))


def test_the_levels_tile_the_lower_triangle_once():
    """Every pair ``j <= i`` of a chunk lies in one level's mask, and
    there its two factors multiply to ``exp(G_i - G_j)``; the diagonal
    level's column factor is the only one above 1."""
    g_sum = jnp.cumsum(-jax.random.uniform(jax.random.key(7), (32, 4)), 0)
    levels = kda._levels(g_sum, 8)
    assert [exact for *_, exact in levels] == [True, False, False]
    cover = sum(mask.astype(jnp.int32) for _, _, mask, _ in levels)
    low_eq = jnp.tril(jnp.ones((32, 32), jnp.int32))
    np.testing.assert_array_equal(cover * low_eq, low_eq)
    want = jnp.exp(g_sum[:, None, :] - g_sum[None, :, :])
    for row_f, col_f, mask, exact in levels:
        both = row_f[:, None, :] * col_f[None, :, :]
        keep = (mask & (low_eq > 0))[..., None]
        np.testing.assert_allclose(jnp.where(keep, both, 0.0),
                                   jnp.where(keep, want, 0.0), rtol=1e-5)
        assert float(row_f.max()) <= 1.0
        assert (float(col_f.max()) > 1.0) == exact


@pytest.mark.parametrize("scale", [0.3, 0.5, 0.8])
def test_the_inverse_by_blocks_is_exact_where_doublings_are_not(scale):
    """``a = -scale x`` (ones below the diagonal) at 128: ``(I - a)^-1``
    has entries of at most 1, the powers doublings form reach 1e14 and
    past 1e30, and float32 keeps none of the cancellation; forward
    substitution over blocks of 16 keeps it all."""
    a = -scale * jnp.tril(jnp.ones((128, 128), jnp.float32), -1)
    want = np.linalg.inv(np.eye(128) - np.asarray(a, np.float64))
    assert np.abs(want).max() <= 1.0
    got = jax.jit(lambda a: kda._unit_lower_inverse_by_blocks(a, 16))(a)
    np.testing.assert_allclose(got, want, atol=3e-4)
    assert float(np.abs(np.asarray(jax.jit(gd._unit_lower_inverse)(a))
                        - want).max()) > 1e5
    # a chunk that is one block is the doublings, which are sound there
    small = a[:16, :16]
    np.testing.assert_allclose(
        jax.jit(lambda a: kda._unit_lower_inverse_by_blocks(a, 16))(small),
        want[:16, :16], atol=1e-4)


def test_keys_that_resemble_one_another_under_a_slow_decay_come_out_right():
    """What broke the cell's second layer on the chip at a chunk of 128
    (PR 41): keys with a cosine of 0.9 to one another, a decay of 0.99 a
    position, beta near 1. Forward and every gradient against the
    scan."""
    ks = jax.random.split(jax.random.key(0), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = (1, 256, 2, 32)
    common = jax.random.normal(ks[5], (1, 1, 2, 32))
    q = unit(common + 0.3 * jax.random.normal(ks[0], shape)) * 32 ** -0.5
    k = unit(common + 0.3 * jax.random.normal(ks[1], shape))
    assert float(jnp.mean(jnp.einsum("bshd,bthd->bhst", k, k))) > 0.9
    v = jax.random.normal(ks[2], shape)
    g = -0.01 * jax.nn.sigmoid(jax.random.normal(ks[3], shape))
    beta = 0.5 + 0.45 * jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    weight = jax.random.normal(ks[6], shape)
    got, got_grads = out_and_vjp(
        lambda *a: kda.kimi_delta_attention(*a, chunk=128, sub=16,
                                            interpret=True),
        weight, q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        want, want_grads = out_and_vjp(kda.kda_reference, weight,
                                       q, k, v, g, beta)
    np.testing.assert_allclose(got, want, rtol=1e-3,
                               atol=1e-4 * float(jnp.abs(want).max()))
    for name, g_, w in zip(("q", "k", "v", "g", "beta"), got_grads,
                           want_grads):
        np.testing.assert_allclose(
            g_, w, rtol=1e-2, atol=1e-3 * float(jnp.abs(w).max()),
            err_msg=f"d{name}")


def test_the_ladder_gives_powers_of_two_that_hold_a_short_sequence():
    chunk, sub = kda._CHUNK_LADDER[-1][1:]
    assert kda._lengths_for(16384) == (chunk, sub)
    assert kda._lengths_for(chunk + 1) == (chunk, sub)
    assert kda._lengths_for(20) == (32, min(sub, 32))
    assert kda._lengths_for(3) == (8, 8)
    assert sub <= 16        # what the published bound of -5 allows


def test_a_call_the_rule_cannot_serve_is_refused():
    q, k, v, g, beta = operands(5, 1, 16, 2, 8, 8)
    with pytest.raises(ValueError, match="powers of two"):
        kda.kimi_delta_attention(q, k, v, g, beta, chunk=12)
    with pytest.raises(ValueError, match="powers of two"):
        kda.kimi_delta_attention(q, k, v, g, beta, chunk=8, sub=16)
    with pytest.raises(ValueError, match="want"):
        kda.kimi_delta_attention(q, k, v, g[..., 0], beta)
    with pytest.raises(ValueError, match="want"):
        kda.kimi_delta_attention(q, k, v[:, :, :1], g, beta)


def test_the_traced_call_leaves_its_chunks_in_the_gauge():
    from horovod_tpu.common import basics
    noted = {}
    real = basics.note_traced
    basics.note_traced = lambda name, what, kinds: noted.update(
        {name: kinds})
    try:
        kda._note_chunks(100, 32, 16, 16, 8)
    finally:
        basics.note_traced = real
    # a head's chunk keeps its entering state [8, 16] and its inverse
    # [32, 32], float32, for the backward
    assert noted == {"hvd_kda_chunks": {
        "chunks": 4, "chunk_length": 32, "sub_block_length": 16,
        "kept_bytes_per_chunk": 4 * (16 * 8 + 32 * 32)}}
