"""The chunked head loss (``models/transformer.py``
``lm_loss_from_hidden``) makes its gradient in the pass that makes the
loss. Held against ``jax.grad`` of the plain two-pass chunked form it
replaced, kept here as the reference: value and both gradients, and the
count of vocabulary-sized products each form lowers to."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from horovod_tpu.compat import jaxshim  # noqa: E402
from horovod_tpu.models.transformer import lm_loss_from_hidden  # noqa: E402

pytestmark = [pytest.mark.fast, pytest.mark.interpreter_of_its_own]

BATCH, WIDTH, VOCAB, CHUNK = 2, 16, 50, 8


def _two_pass(hidden, head_kernel, tokens, chunk=1024):
    """The form before: a rematerialized scan body that autodiff
    differentiates, so the backward makes each chunk's logits again."""
    targets = tokens[:, 1:]
    hid = hidden[:, :-1]
    b, s, d = hid.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    mask = jnp.ones((b, s), jnp.float32)
    if pad:
        hid = jnp.pad(hid, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (s + pad) // chunk
    hid = hid.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    targets = targets.reshape(b, n, chunk).transpose(1, 0, 2)
    mask = mask.reshape(b, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_ll(h, t, m):
        logits = h.astype(jnp.float32) @ head_kernel
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, t[..., None], axis=-1)[..., 0]
        return jnp.sum(ll * m)

    def body(carry, xs):
        h, t, m = xs
        return carry + chunk_ll(h, t, m), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hid, targets, mask))
    return -total / (b * s)


def _inputs(seq, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(BATCH, seq, WIDTH), dtype),
            jnp.asarray(0.3 * rng.randn(WIDTH, VOCAB), jnp.float32),
            jnp.asarray(rng.randint(0, VOCAB, (BATCH, seq)), jnp.int32))


def _plain(loss):
    return lambda h, w, t: loss(h, w, t, chunk=CHUNK)


def _tied(loss):
    """The table arrives as an embedding's transpose; the gradient is
    the embedding's, [vocab, width]."""
    return lambda h, e, t: loss(h, e.T, t, chunk=CHUNK)


def _weighted(loss):
    """A cotangent that is not 1: the sparse decoder's second call."""
    return lambda h, w, t: 0.3 * loss(h, w, t, chunk=CHUNK)


def _through_the_trainer(loss):
    """``make_chunked_lm_loss`` over an ``apply_fn`` that hands back the
    hidden states it was given as a parameter; the reference takes the
    same arguments directly."""
    if loss is _two_pass:
        return _plain(loss)
    from horovod_tpu.parallel import make_chunked_lm_loss
    chunked = make_chunked_lm_loss(chunk=CHUNK)

    def apply_fn(params, tokens, mutable, return_hidden):
        assert return_hidden
        return params["params"]["hidden"], {}

    return lambda h, w, t: chunked(
        apply_fn, {"params": {"hidden": h, "lm_head": {"kernel": w}}},
        {"tokens": t})


def _differentiated(f):
    return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))


def _under_shard_map(f):
    """``jit(shard_map(value_and_grad))`` over a mesh of one, as
    ``train_steps._loss_train_step`` differentiates."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    rep = jaxshim.partition_spec()
    return jax.jit(jaxshim.shard_map(
        jax.value_and_grad(f, argnums=(0, 1)), mesh=mesh,
        in_specs=(rep, rep, rep), out_specs=(rep, (rep, rep))))


# (id, hidden's type, length, how the loss is called, how it is
# differentiated); 33 tokens are 32 predictions, four whole chunks of 8
_CASES = [
    ("float32", jnp.float32, 33, _plain, _differentiated),
    ("bfloat16", jnp.bfloat16, 33, _plain, _differentiated),
    ("a-padded-tail", jnp.float32, 30, _plain, _differentiated),
    ("a-padded-tail-bfloat16", jnp.bfloat16, 22, _plain, _differentiated),
    ("shorter-than-a-chunk", jnp.float32, 6, _plain, _differentiated),
    ("a-tied-table", jnp.bfloat16, 33, _tied, _differentiated),
    ("a-cotangent-of-0.3", jnp.float32, 33, _weighted, _differentiated),
    ("a-cotangent-of-0.3-bfloat16", jnp.bfloat16, 30, _weighted,
     _differentiated),
    ("shard-map-over-one", jnp.bfloat16, 33, _plain, _under_shard_map),
    ("make-chunked-lm-loss", jnp.float32, 30, _through_the_trainer,
     _differentiated),
]


@pytest.mark.parametrize("dtype,seq,call,grad",
                         [c[1:] for c in _CASES], ids=[c[0] for c in _CASES])
def test_value_and_gradients_match_the_two_pass_form(dtype, seq, call, grad):
    hidden, table, tokens = _inputs(seq, dtype)
    if call is _tied:
        table = table.T
    got, (got_h, got_w) = grad(call(lm_loss_from_hidden))(
        hidden, table, tokens)
    want, (want_h, want_w) = grad(call(_two_pass))(hidden, table, tokens)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got_h.dtype == want_h.dtype == dtype
    assert got_w.shape == want_w.shape == table.shape
    assert got_w.dtype == want_w.dtype == jnp.float32
    np.testing.assert_allclose(got_w, want_w, rtol=1e-5, atol=1e-8)
    # the hidden's gradient is rounded to the hidden's type: one step of
    # bfloat16 where a cotangent is multiplied in after the rounding
    step = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got_h, np.float32),
                               np.asarray(want_h, np.float32),
                               rtol=step, atol=1e-8)
    # the padding and the last position (no target) get no gradient
    assert not np.asarray(got_h, np.float32)[:, -1].any()


def test_a_bfloat16_table_gets_a_bfloat16_gradient():
    """The check's control hands every parameter over in bfloat16: the
    gradient takes the table's type. It is added up in float32 over the
    chunks and rounded once (autodiff added it up in the table's type),
    so the reference is the same table's values held in float32."""
    hidden, table, tokens = _inputs(33, jnp.bfloat16)
    table = table.astype(jnp.bfloat16)
    _, (got_h, got_w) = _differentiated(_weighted(lm_loss_from_hidden))(
        hidden, table, tokens)
    _, (want_h, want_w) = _differentiated(_weighted(_two_pass))(
        hidden, table.astype(jnp.float32), tokens)
    assert got_h.dtype == got_w.dtype == jnp.bfloat16
    for got, want in ((got_h, want_h), (got_w, want_w)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2.0 ** -7, atol=1e-8)


def _products(jaxpr, macs):
    """``dot_general``s of ``macs`` multiply-adds in a jaxpr, sub-jaxprs
    (scan bodies, ``jit``s, rematerialized calls) included."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            count += (int(np.prod(eqn.outvars[0].aval.shape))
                      * int(np.prod([lhs[i] for i in contract]))) == macs
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else (param,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    count += _products(sub, macs)
    return count


def test_the_gradient_takes_three_vocabulary_sized_products_a_chunk():
    """[tokens, d] x [d, V]-sized products in the scan bodies: the
    logits, ``dlogits @ W^T`` and ``h^T @ dlogits`` where the two-pass
    form makes the logits a second time; the undifferentiated call
    makes the logits and neither gradient product."""
    hidden, table, tokens = _inputs(33)
    macs = BATCH * CHUNK * WIDTH * VOCAB

    def count(f):
        return _products(jax.make_jaxpr(f)(hidden, table, tokens).jaxpr,
                         macs)

    grad = lambda loss: jax.value_and_grad(_plain(loss), argnums=(0, 1))
    assert count(grad(lm_loss_from_hidden)) == 3
    assert count(grad(_two_pass)) == 4
    assert count(_plain(lm_loss_from_hidden)) == 1
    assert count(jax.jit(_plain(lm_loss_from_hidden))) == 1


def test_a_differentiated_call_writes_its_chunks_into_the_registry(
        monkeypatch):
    import horovod_tpu.jax as hvd
    from horovod_tpu import metrics
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    hvd.init()
    try:
        hidden, table, tokens = _inputs(30)
        _plain(lm_loss_from_hidden)(hidden, table, tokens)
        assert not [name for name in metrics()["local"]
                    if name.startswith("hvd_head_loss_chunks")]
        jax.grad(_plain(lm_loss_from_hidden))(hidden, table, tokens)
        local = metrics()["local"]
        assert local['hvd_head_loss_chunks{kind="chunks"}']["v"] == 4
        assert local['hvd_head_loss_chunks{kind="chunk_length"}']["v"] == 8
        assert local['hvd_head_loss_chunks{kind="products"}']["v"] == 3
    finally:
        hvd.shutdown()
