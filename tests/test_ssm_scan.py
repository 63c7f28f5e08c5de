"""The selective scan's kernels (``horovod_tpu/parallel/ssm_scan.py``)
in interpret mode against the literal recurrence, forward and all six
gradients, at lengths that are and are not a multiple of the chunk and
at channel counts that fill registers and do not."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from horovod_tpu.parallel import ssm_scan as ss  # noqa: E402

from .compiled import out_and_vjp  # noqa: E402

pytestmark = [pytest.mark.fast, pytest.mark.interpreter_of_its_own]


def _case(seed, bt, seq, channels, states, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    return (mk(bt, seq, channels).astype(dtype),
            jax.nn.softplus(mk(bt, seq, channels)),
            -jnp.exp(mk(channels, states)), mk(bt, seq, states),
            mk(bt, seq, states), mk(channels))


# (id, batch, length, channels, states, chunk)
_CASES = [
    ("whole-chunks", 2, 32, 32, 4, 16),
    ("a-ragged-tail", 2, 40, 32, 4, 16),
    ("shorter-than-a-chunk", 1, 5, 16, 2, 8),
    ("registers-and-two-blocks", 1, 17, 2048, 2, 8),
    ("the-cells-states", 1, 9, 16, 16, 4),
]


@pytest.mark.parametrize("bt,seq,channels,states,chunk",
                         [c[1:] for c in _CASES], ids=[c[0] for c in _CASES])
def test_kernels_match_the_literal_recurrence(bt, seq, channels, states,
                                              chunk):
    args = _case(3, bt, seq, channels, states)
    g = jnp.asarray(np.random.RandomState(4).randn(bt, seq, channels),
                    jnp.float32)
    y, grads = out_and_vjp(lambda *x: ss.selective_scan(
        *x, chunk=chunk, interpret=True), g, *args)
    want, grads_want = out_and_vjp(ss.selective_scan_reference, g, *args)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    for got, ref, name in zip(grads, grads_want,
                              ("u", "delta", "A", "B", "C", "D")):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        scale = float(jnp.abs(ref).max()) + 1.0
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6 * scale,
                                   err_msg=f"d{name}")


def test_the_state_is_float32_whatever_the_operands():
    """bfloat16 ``u`` in, bfloat16 ``y`` out; inside, the float32
    recurrence on the rounded ``u``."""
    args = _case(5, 1, 24, 32, 4, jnp.bfloat16)
    y = ss.selective_scan(*args, chunk=8, interpret=True)
    assert y.dtype == jnp.bfloat16
    want = ss.selective_scan_reference(*args)
    np.testing.assert_allclose(y.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2 * float(jnp.abs(want).max()))


def test_the_state_carries_over_every_chunk():
    """A decay near one: position 0's input still shows at the last
    position, eleven chunks on."""
    bt, seq, channels, states = 1, 44, 8, 2
    u = jnp.zeros((bt, seq, channels)).at[:, 0].set(1.0)
    delta = jnp.full((bt, seq, channels), 0.1)
    a = jnp.full((channels, states), -0.01)
    ones = jnp.ones((bt, seq, states))
    y = ss.selective_scan(u, delta, a, ones, ones, jnp.zeros((channels,)),
                          chunk=4, interpret=True)
    want = states * 0.1 * np.exp(-0.001 * (seq - 1))
    np.testing.assert_allclose(y[0, -1], want, rtol=1e-5)


def test_shapes_are_checked_and_the_ladder_picks_the_chunk():
    args = list(_case(6, 1, 8, 16, 2))
    with pytest.raises(ValueError, match=r"want \[B,S,D\]"):
        ss.selective_scan(args[0], args[1][:, :4], *args[2:], interpret=True)
    assert ss._chunk_for(16384) == 128 == ss._CHUNK_LADDER[-1][1]
    assert ss._chunk_for(64) == 64 and ss._chunk_for(20) == 24
    assert ss._channel_tile(5120) == (5, 8, 128)
    assert ss._channel_tile(256) == (1, 2, 128)
    assert ss._channel_tile(32) == (1, 1, 32)


def test_a_traced_call_writes_its_chunks_into_the_registry(monkeypatch):
    import horovod_tpu.jax as hvd
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    hvd.init()
    try:
        args = _case(7, 1, 40, 16, 2)
        ss.selective_scan(*args, chunk=16, interpret=True)
        from horovod_tpu import metrics
        local = metrics()["local"]
        assert local['hvd_ssm_scan_chunks{kind="chunks"}']["v"] == 3
        assert local['hvd_ssm_scan_chunks{kind="chunk_length"}']["v"] == 16
    finally:
        hvd.shutdown()
