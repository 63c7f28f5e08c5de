"""head_dim-aware flash-attention tile ladder (ADVICE r05): the
512x1024 default block pair is measured up to D = 256 (PR 27 read the
halved pair 27% slower there on the chip); past that the kernels'
per-program VMEM working set grows linearly with D, so the ladder
must shrink as D doubles. These tests pin the
selection logic across a (seq, head_dim) sweep and prove the scaled
tiles still compute the exact attention (interpret mode on CPU)."""

import numpy as np
import pytest

from horovod_tpu.parallel.flash_attention import (
    _BLOCK_K_LADDER, _BLOCK_Q_LADDER, _auto_block, _ladders_for,
)


def _blocks_for(seq_q, seq_k, head_dim):
    ql, kl = _ladders_for(head_dim)
    return _auto_block(seq_q, ql, None), _auto_block(seq_k, kl, None)


def test_default_ladder_unchanged_up_to_256():
    """D <= 256 keeps the measured 512x1024 defaults exactly — the
    ladder change must not perturb validated configurations."""
    for d in (32, 64, 96, 128, 192, 256):
        assert _ladders_for(d) == (_BLOCK_Q_LADDER, _BLOCK_K_LADDER)
    assert _blocks_for(2048, 2048, 128) == (512, 1024)
    assert _blocks_for(512, 1024, 64) == (512, 1024)


def test_ladder_halves_per_doubling_past_256():
    assert _ladders_for(512) == ((256, 128), (512, 256, 128))
    assert _ladders_for(1024) == ((128,), (256, 128))
    # floor: tiles never shrink below the 128-lane MXU width
    assert _ladders_for(2048) == ((128,), (128,))
    assert _ladders_for(4096) == ((128,), (128,))


def test_working_set_stays_roughly_d_invariant():
    """The point of the ladder: (block_q + 2*block_k) * D — the
    resident q/k/v tile footprint — must not grow with D beyond the
    validated D=256 envelope (floor-limited tails excepted)."""
    base_q, base_k = _blocks_for(4096, 4096, 256)
    base = (base_q + 2 * base_k) * 256
    for d in (512, 1024):
        bq, bk = _blocks_for(4096, 4096, d)
        assert (bq + 2 * bk) * d <= base, (d, bq, bk)


def test_auto_block_divisibility_sweep():
    """Across the sweep, the chosen blocks always divide the sequence
    when any ladder entry does (graceful degradation contract)."""
    for d in (64, 128, 256, 512):
        ql, kl = _ladders_for(d)
        for seq in (128, 256, 384, 512, 1024, 1536, 2048, 4096):
            bq = _auto_block(seq, ql, None)
            bk = _auto_block(seq, kl, None)
            if any(seq % b == 0 for b in ql):
                assert seq % bq == 0, (d, seq, bq)
            if any(seq % b == 0 for b in kl):
                assert seq % bk == 0, (d, seq, bk)
            # explicit blocks always win
            assert _auto_block(seq, ql, 32) == 32


def test_explicit_blocks_still_override():
    assert _auto_block(2048, _ladders_for(512)[0], 256) == 256


@pytest.mark.parametrize("head_dim", [160, 256])
def test_flash_matches_dense_at_large_head_dim(head_dim):
    """Numerical proof at D > 128: the auto-picked (scaled) tiles
    compute the same causal attention as the dense reference. Small
    sequence so interpret mode stays fast; D is the variable under
    test."""
    jnp = pytest.importorskip("jax.numpy")
    from horovod_tpu.parallel.flash_attention import flash_attention
    rng = np.random.RandomState(11)
    b, s, h = 1, 256, 1
    q = jnp.asarray(rng.randn(b, s, h, head_dim) * 0.1, jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, head_dim) * 0.1, jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, head_dim) * 0.1, jnp.float32)
    out = flash_attention(q, k, v, causal=True, interpret=True)

    qf = np.asarray(q, np.float64)[:, :, 0]
    kf = np.asarray(k, np.float64)[:, :, 0]
    vf = np.asarray(v, np.float64)[:, :, 0]
    scores = np.einsum("bqd,bkd->bqk", qf, kf) / np.sqrt(head_dim)
    mask = np.tril(np.ones((s, s), bool))
    scores = np.where(mask[None], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bqk,bkd->bqd", p, vf)
    np.testing.assert_allclose(np.asarray(out)[:, :, 0], ref,
                               atol=3e-5)
