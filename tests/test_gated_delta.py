"""The gated delta rule's kernels
(``horovod_tpu/parallel/gated_delta.py``) in interpreter mode against
the literal recurrence: forward and every gradient (q, k, v, g, beta),
at lengths that are and are not a multiple of the chunk, with a key
head serving one value head and two, at heads off the lane tile in the
ratio 1 : 2 laid out behind zeros, with ``beta`` in (0, 2) and the
chunk's inverse by blocks; and the chunked equations the kernels
compute, in plain ``jax.numpy``, against the same recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import gated_delta as gd

from .compiled import out_and_vjp

pytestmark = [pytest.mark.fast, pytest.mark.time_limit(120),
              pytest.mark.interpreter_of_its_own]


def operands(seed, batch, seq, key_heads, value_heads, dk, dv,
             dtype=jnp.float32, beta_max=1.0):
    """q, k as the layer hands them over (L2-normalised, q scaled), v,
    a decay's logarithm around -0.1 and beta in (0, ``beta_max``)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (batch, seq, key_heads, dk))) \
        * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, seq, key_heads, dk)))
    v = jax.random.normal(ks[2], (batch, seq, value_heads, dv))
    g = -0.1 * jnp.exp(jax.random.normal(ks[3], (batch, seq, value_heads)))
    beta = beta_max * jax.nn.sigmoid(
        jax.random.normal(ks[4], (batch, seq, value_heads)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def chunked(q, k, v, g, beta, chunk):
    """The chunked form of the module's docstring, a value head and a
    chunk at a time, in plain ``jax.numpy`` (float32, the inverse by
    ``jnp.linalg``)."""
    rep = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(x, rep, axis=2) for x in (q, k))
    seq = q.shape[1]
    low = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    low_eq = jnp.tril(jnp.ones((chunk, chunk), bool))

    def head(q, k, v, g, beta):                     # [S, D] ..., [S]
        state = jnp.zeros((q.shape[1], v.shape[1]))
        outs = []
        for s in range(0, seq, chunk):
            qc, kc, vc = q[s:s + chunk], k[s:s + chunk], v[s:s + chunk]
            bc = beta[s:s + chunk, None]
            run = jnp.cumsum(g[s:s + chunk])
            decay = jnp.exp(run[:, None] - run[None, :])
            a = jnp.where(low, -bc * (kc @ kc.T) * decay, 0.0)
            t = jnp.linalg.inv(jnp.eye(chunk) - a)
            w = t @ (bc * jnp.exp(run)[:, None] * kc)
            u = t @ (bc * vc)
            v_new = u - w @ state
            outs.append((qc * jnp.exp(run)[:, None]) @ state
                        + jnp.where(low_eq, (qc @ kc.T) * decay, 0.0) @ v_new)
            state = jnp.exp(run[-1]) * state \
                + (kc * jnp.exp(run[-1] - run)[:, None]).T @ v_new
        return jnp.concatenate(outs)

    heads = jax.vmap(head, in_axes=(1, 1, 1, 1, 1), out_axes=1)
    return jax.vmap(heads)(q, k, v, g, beta)


def test_the_chunked_equations_are_the_recurrence():
    args = operands(1, 2, 32, 2, 4, 8, 6)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            jax.jit(lambda *a: chunked(*a, chunk=8))(*args),
            jax.jit(gd.gated_delta_rule_reference)(*args),
            rtol=2e-5, atol=2e-6)


# (case, sequence, chunk, key heads, value heads, Dk, Dv, beta's upper
#  end, the lanes a head is laid out to: None as it comes)
CASES = [("whole_chunks", 32, 16, 2, 4, 16, 8, 1.0, None),
         ("a_ragged_tail", 40, 16, 2, 4, 16, 8, 1.0, None),
         ("one_value_head_a_key_head", 24, 8, 2, 2, 8, 16, 1.0, None),
         ("shorter_than_a_chunk", 11, 16, 1, 2, 8, 8, 1.0, None),
         # Olmo-Hybrid's ratio: a key head of 12 over one value head of
         # 24, laid out to 16 and 32 lanes, beta up to 2
         ("heads_off_the_lane_tile_beta_to_two", 40, 16, 2, 2, 12, 24, 2.0,
          16)]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_the_kernels_are_the_recurrence_forward_and_backward(case):
    _, seq, chunk, hk, hv, dk, dv, beta_max, lane = case
    args = operands(2, 2, seq, hk, hv, dk, dv, beta_max=beta_max)
    weight = jax.random.normal(jax.random.key(9), (2, seq, hv, dv))
    got, got_grads = out_and_vjp(
        lambda *a: gd.gated_delta_rule(*a, chunk=chunk, interpret=True,
                                       beta_max=beta_max, lane=lane),
        weight, *args)
    want, want_grads = out_and_vjp(gd.gated_delta_rule_reference, weight,
                                   *args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for name, g, w in zip(("q", "k", "v", "g", "beta"), got_grads,
                          want_grads):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()),
            err_msg=f"d{name}")


def kernel_products(call, name, *args):
    """The ``dot_general``s in the body of the Pallas kernel ``name``
    that ``call(*args)`` traces, as ``(all, at Precision.HIGHEST)``."""
    found = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            mine = inside or (eqn.primitive.name == "pallas_call"
                              and name in str(eqn.params["name"]))
            if inside and eqn.primitive.name == "dot_general":
                # None, or a pair: one precision an operand
                found.append(set(eqn.params["precision"] or ())
                             == {jax.lax.Precision.HIGHEST})
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, mine)

    walk(jax.make_jaxpr(call)(*args).jaxpr, False)
    return len(found), sum(found)


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_the_forward_hands_the_backward_each_chunks_inverse(case):
    """``gdn_fwd``'s third output is ``T = (I - A)^-1`` of every value
    head's chunk, by the doublings or, with ``beta`` above 1, by blocks:
    against the same function of the same ``A`` made outside the kernel
    from the operands as the kernels get them. The backward kernel reads
    it and takes no inverse: its only products at full precision are
    ``dA = T^T dT T^T``'s two a value head, where the forward's are the
    inverse's."""
    _, seq, chunk, hk, hv, dk, dv, beta_max, lane = case
    rep = hv // hk
    solve = min(chunk, gd._SOLVE_BLOCK) if beta_max > 1.0 else None
    args = operands(2, 2, seq, hk, hv, dk, dv, beta_max=beta_max)
    ops = gd._laid_out(*args, chunk, lane or 1)
    static = dict(chunk=chunk, heads=(hk, hv), interpret=True)
    out, sent, t = gd._gdn_fwd(*ops, solve=solve, **static)
    n_chunks = -(-seq // chunk)
    assert t.shape == (2, hv, n_chunks, chunk, chunk) \
        and t.dtype == jnp.float32 and sent.shape[:3] == t.shape[:3]

    @jax.jit
    def outside(k, g_sum, beta):
        k = k.reshape(2, n_chunks, chunk, hk, -1).transpose(0, 3, 1, 2, 4)
        kk = jnp.repeat(jnp.einsum("bhcid,bhcjd->bhcij", k, k), rep, axis=1)
        decay = jnp.exp(jnp.minimum(g_sum[..., :, None] - g_sum[..., None, :],
                                    0.0))
        a = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool), -1),
                      -beta[..., :, None] * kk * decay, 0.0)
        inverse = gd._unit_lower_inverse if solve is None else (
            lambda a: gd._unit_lower_inverse_by_blocks(a, solve))
        return jax.lax.map(inverse, a.reshape(-1, chunk, chunk)).reshape(
            a.shape)

    with jax.default_matmul_precision("highest"):
        want = outside(ops[1], ops[3], ops[4])
    np.testing.assert_allclose(t, want, rtol=1e-5, atol=1e-6)
    fwd = kernel_products(
        lambda *o: gd._gdn_fwd(*o, solve=solve, **static), "gdn_fwd", *ops)
    bwd = kernel_products(
        lambda *o: gd._gdn_bwd(*o, **static), "gdn_bwd", *ops, sent, t, out)
    assert bwd[1] == 2 * rep and fwd[1] > bwd[1], (fwd, bwd)
    assert gd.kept_bytes(chunk, *sent.shape[3:]) \
        == 4 * (sent[0, 0, 0].size + t[0, 0, 0].size)


def test_bfloat16_operands_keep_the_state_and_the_gates_in_float32():
    """The model's call: q, k, v in bfloat16. The result is within a
    bfloat16's rounding of the float32 recurrence on the same rounded
    operands, and comes back in v's type."""
    args = operands(3, 1, 48, 2, 4, 16, 16, jnp.bfloat16)
    got = jax.jit(lambda *a: gd.gated_delta_rule(
        *a, chunk=16, interpret=True))(*args)
    want = jax.jit(gd.gated_delta_rule_reference)(*args)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=0.05,
                               atol=0.02 * float(jnp.abs(want).max()))


def test_the_unit_lower_inverse_is_exact_for_a_nilpotent_matrix():
    a = jnp.tril(jax.random.normal(jax.random.key(4), (32, 32)) * 0.3, -1)
    np.testing.assert_allclose(
        jax.jit(gd._unit_lower_inverse)(a),
        np.linalg.inv(np.eye(32) - np.asarray(a, np.float64)),
        rtol=2e-5, atol=2e-5)


def resembling_keys(seed, chunk, dim, cosine):
    """``chunk`` unit keys whose mean cosine to one another is
    ``cosine``."""
    kb, kn = jax.random.split(jax.random.key(seed))
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return unit(cosine ** 0.5 * unit(jax.random.normal(kb, (dim,)))
                + (1 - cosine) ** 0.5 * unit(
                    jax.random.normal(kn, (chunk, dim))))


@pytest.mark.parametrize("cosine", [0.5, 0.95])
def test_the_inverse_by_blocks_holds_where_doublings_lose_it(cosine):
    """The chunk the kernels run at 8,192 positions (128), keys of 96
    that resemble one another, ``beta`` in (1, 2), a decay near zero:
    the entries of ``A`` share a sign and reach 2, the doublings'
    powers grow past float32 and the inverse is lost in their
    cancellation; forward substitution over blocks of ``_SOLVE_BLOCK``
    rows stays within 1e-4 of the float64 inverse."""
    chunk = gd._CHUNK_LADDER[-1][1]
    k = resembling_keys(6, chunk, 96, cosine)
    beta = jnp.linspace(1.0, 2.0, chunk)[:, None]
    run = jnp.cumsum(jnp.full((chunk,), -0.01))
    a = jnp.tril(-beta * (k @ k.T) * jnp.exp(run[:, None] - run[None]), -1)
    exact = np.linalg.inv(np.eye(chunk) - np.asarray(a, np.float64))
    by_blocks = jax.jit(lambda a: gd._unit_lower_inverse_by_blocks(
        a, gd._SOLVE_BLOCK))(a)
    np.testing.assert_allclose(by_blocks, exact, rtol=0,
                               atol=1e-4 * np.abs(exact).max())
    doubled = np.asarray(jax.jit(gd._unit_lower_inverse)(a))
    assert not np.allclose(doubled, exact, rtol=0,
                           atol=0.1 * np.abs(exact).max())


def test_beta_above_one_takes_the_inverse_by_blocks_on_resembling_keys():
    """The rule itself on such keys over four chunks of 32: within 1e-4
    of the recurrence with ``beta_max`` 2 (blocks)."""
    q, _, v, g, beta = operands(7, 1, 128, 1, 1, 96, 16, beta_max=2.0)
    k = resembling_keys(8, 128, 96, 0.8)[None, :, None]
    args = (q, k, v, 0.1 * g, beta)
    want = jax.jit(gd.gated_delta_rule_reference)(*args)
    got = jax.jit(lambda *a: gd.gated_delta_rule(
        *a, chunk=32, interpret=True, beta_max=2.0))(*args)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-4 * float(jnp.abs(want).max()))


def test_heads_are_laid_out_behind_zeros_and_taken_back():
    x = jax.random.normal(jax.random.key(3), (2, 5, 4 * 12))
    laid = gd.lay_heads(x, 12, lane=16)
    assert laid.shape == (2, 5, 4 * 16) == (2, 5, gd.laid_columns(48, 12, 16))
    np.testing.assert_array_equal(
        laid.reshape(2, 5, 4, 16)[..., :12], x.reshape(2, 5, 4, 12))
    assert not np.any(laid.reshape(2, 5, 4, 16)[..., 12:])
    np.testing.assert_array_equal(gd.take_heads(laid, 12, lane=16), x)
    assert gd.lay_heads(x, 16, lane=16) is x        # whole lanes: as it is
    assert gd.take_heads(x, 16, lane=16) is x
    assert gd.laid_columns(2880, 96) == 3840 and gd.laid_columns(256, 128) \
        == 256


def test_the_traced_call_leaves_its_layout_in_the_gauge(monkeypatch):
    from horovod_tpu.common import basics
    noted = {}
    monkeypatch.setattr(basics, "note_traced", lambda name, what, kinds:
                        noted.update({name: kinds}))
    args = operands(5, 1, 16, 2, 2, 12, 24)
    gd.gated_delta_rule(*args, chunk=8, lane=16)
    assert noted["hvd_gdn_layout"] == {
        "key_dim": 12, "value_dim": 24, "laid_key_dim": 16,
        "laid_value_dim": 32}
    # operands that come laid out already say what is no padding
    laid = [gd.lay_heads(x, 12, lane=16) for x in args[:3]]
    gd.gated_delta_rule(*laid, *args[3:], chunk=8, filled=(12, 24))
    assert noted["hvd_gdn_layout"] == {
        "key_dim": 12, "value_dim": 24, "laid_key_dim": 16,
        "laid_value_dim": 32}
    # a head's chunk keeps its entering state [16, 32] and its inverse
    # [8, 8], float32, for the backward
    assert noted["hvd_gdn_chunks"] == {
        "chunks": 2, "chunk_length": 8,
        "kept_bytes_per_chunk": 4 * (16 * 32 + 8 * 8)}


def test_the_ladder_gives_a_power_of_two_that_holds_a_short_sequence():
    top = gd._CHUNK_LADDER[-1][1]
    assert gd._chunk_for(16384) == top
    assert gd._chunk_for(top + 1) == top
    assert gd._chunk_for(20) == min(top, 32)
    assert gd._chunk_for(3) == 8


def test_a_call_the_rule_cannot_serve_is_refused():
    q, k, v, g, beta = operands(5, 1, 16, 2, 4, 8, 8)
    with pytest.raises(ValueError, match="power of two"):
        gd.gated_delta_rule(q, k, v, g, beta, chunk=12)
    with pytest.raises(ValueError, match="want"):
        gd.gated_delta_rule(q, k, v[:, :, :3], g[..., :3], beta[..., :3])
    with pytest.raises(ValueError, match="want"):
        gd.gated_delta_rule(q, k, v, g[..., :2], beta)


def test_the_chunked_forms_products_exceed_the_recurrences_count():
    """``chunk_flops`` by hand at the model's shape (chunk 64, heads of
    128, two value heads a key head)."""
    c, d = 64, 128
    shared = 2 * (2 * c * c * d)                      # K K^T, Q K^T
    inverse = 10 * 2 * c ** 3                         # 5 squarings, 5 products
    head = inverse + 2 * (2 * c * c * d) + 2 * (2 * c * d * d) \
        + 2 * c * c * d + 2 * c * d * d
    assert gd.chunk_flops(64, 128, 128, 2) == shared + 2 * head
    per_position_and_head = gd.chunk_flops(64, 128, 128, 2) / (2 * c)
    assert 2.1 < per_position_and_head / (7 * d * d) < 2.2
