"""The flash kernels with a window, with fewer key-value heads than
query heads, and with a value head of another size than the key head
(PR 31): each against ``_dense_reference``'s mathematics, forward and
all three gradients, in interpret mode; and ``causal_subtile_counts``
of a windowed call against the mask's own definition and against the
blocks the three kernels really form."""

import math

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from horovod_tpu.parallel import flash_attention as fa  # noqa: E402

from .compiled import out_and_vjp  # noqa: E402
from .test_flash_tiles import _counted  # noqa: E402

pytestmark = [pytest.mark.fast, pytest.mark.interpreter_of_its_own]


def _case(seed, b, sq, sk, h, hkv, d, dv):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    return (mk(b, sq, h, d), mk(b, sk, hkv, d), mk(b, sk, hkv, dv),
            mk(b, sq, h, dv))


# (id, B, Sq, Sk, H, Hkv, D, Dv, window, block_q, block_k, q_off, k_off)
_CASES = [
    ("window", 1, 256, 256, 2, 2, 16, 16, 64, 64, 128, 0, 0),
    ("window-one-key", 1, 128, 128, 1, 1, 16, 16, 1, 32, 32, 0, 0),
    ("window-wider-than-tiles", 1, 256, 256, 1, 1, 16, 16, 160, 32, 64,
     0, 0),
    ("window-off-the-grid", 1, 128, 256, 1, 1, 16, 16, 48, 32, 64, 133, 7),
    ("window-ring-shard", 1, 128, 128, 1, 1, 16, 16, 96, 64, 64, 256, 128),
    ("one-kv-head", 1, 128, 128, 4, 1, 16, 16, None, 32, 128, 0, 0),
    ("wide-value", 1, 128, 128, 2, 2, 16, 32, None, 64, 64, 0, 0),
    # the differential attention of phi4flash, small: 4 over 2 heads, a
    # value head twice the key head, a window
    ("phi4flash-window", 1, 256, 256, 4, 2, 16, 32, 64, 64, 128, 0, 0),
    ("phi4flash-full", 2, 128, 128, 4, 2, 16, 32, None, 64, 128, 0, 0),
    ("grouped-not-causal", 1, 128, 128, 4, 2, 16, 32, "full", 64, 64,
     0, 0),
    # the windowed layers of smallthinker, small: whole tiles between the
    # window's two diagonals (``_over_tile``'s unmasked branch under a
    # window) and seven query heads a key-value head
    ("smallthinker-window-7-over-1", 1, 256, 256, 7, 1, 16, 16, 128, 32,
     32, 0, 0),
    ("smallthinker-window-14-over-2", 1, 256, 256, 14, 2, 16, 16, 160, 32,
     64, 0, 0),
]


@pytest.mark.parametrize(
    "b,sq,sk,h,hkv,d,dv,window,bq,bk,q_off,k_off",
    [c[1:] for c in _CASES], ids=[c[0] for c in _CASES])
def test_kernels_match_dense(b, sq, sk, h, hkv, d, dv, window, bq, bk,
                             q_off, k_off):
    causal = window != "full"
    window = window if causal else None
    q, k, v, g = _case(7, b, sq, sk, h, hkv, d, dv)
    kw = dict(causal=causal, q_offset=q_off, k_offset=k_off, window=window)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, block_q=bq, block_k=bk,
                                  interpret=True, **kw)

    def dense(q, k, v):
        return fa._dense_reference(q, k, v, causal, q_off, k_off, window)

    with jax.default_matmul_precision("highest"):
        out, grads = out_and_vjp(flash, g, q, k, v)
        want, grads_want = out_and_vjp(dense, g, q, k, v)
        assert out.shape == (b, sq, h, dv)
        np.testing.assert_allclose(out, want, atol=2e-5)
        for got, ref, name in zip(grads, grads_want, "qkv"):
            assert got.shape == ref.shape
            np.testing.assert_allclose(got, ref, atol=5e-5,
                                       err_msg=f"d{name}")


def test_window_needs_causal_and_heads_must_divide():
    q, k, v, _ = _case(1, 1, 64, 64, 4, 2, 16, 16)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, causal=False, window=8, interpret=True)
    with pytest.raises(ValueError, match="multiple"):
        fa.flash_attention(q[:, :, :3], k, v, interpret=True)


def test_default_call_builds_what_it_built():
    """Equal heads, one head size, no window: the step walks every kv
    tile, reads row b of k for row b of q, and no block carries a fifth
    member (the lowered text at the cells' shapes is held to the
    parent's in PERF.md, PR 31)."""
    steps, tile, fetch = fa._streamed_tiles(None, 512, 1024, 4, True)
    assert steps == 4 and tile(3, 2, None) == 2 and fetch(3, 2, None) == 2
    assert fa._kv_row(1)(5) == 5 and fa._kv_row(2)(5) == 2
    assert all(len(b) == 4 for d in (-512, 0, 512, 1024, 37)
               for b in fa._tile_blocks(d, 512, 1024, 256, 256))


def test_a_window_of_4096_has_whole_tiles_between_its_diagonals():
    """At smallthinker's shape (S 16384, window 4096, a head of 128: a
    windowed call's 1024x1024 tiles, where the causal call keeps
    512x1024) a q tile walks 6 of its 16 kv tiles and a kv tile 6 of
    the 16 q tiles; two positions on the tiles' grid are crossed by a
    diagonal and have a body of their own, and the three between them
    are whole tiles that run unmasked."""
    q = jnp.zeros((1, 16384, 28, 128))
    assert fa._blocks_for(q, q[:, :, :4], None, None) == (512, 1024)
    assert fa._blocks_for(q, q[:, :, :4], None, None, 4096) == (1024, 1024)
    assert fa._blocks_for(q, q[:, :, :4], 512, None, 4096) == (512, 1024)
    # a head of 256 keeps the causal tile under a window too
    wide = jnp.zeros((1, 16384, 2, 256))
    assert fa._blocks_for(wide, wide, None, None, 4096) == (512, 1024)
    offs = jnp.zeros((2,), jnp.int32)
    steps, tile, fetch = fa._streamed_tiles(4096, 1024, 1024, 16, True)
    assert steps == 6
    assert [int(tile(10, s, offs)) for s in range(6)] == [6, 7, 8, 9, 10, 11]
    assert [int(fetch(10, s, offs)) for s in range(6)] == [6, 7, 8, 9, 10, 10]
    steps, tile, fetch = fa._streamed_tiles(4096, 1024, 1024, 16, False)
    assert steps == 6
    assert [int(tile(3, s, offs)) for s in range(6)] == [3, 4, 5, 6, 7, 8]
    assert [int(fetch(3, s, offs)) for s in range(6)] == [3, 4, 5, 6, 7, 7]
    assert fa._window_positions(1024, 1024, 4096) == (0, 4096)
    whole = [d for d in range(0, 4096 + 1024, 1024)
             if fa._tile_blocks(d, 1024, 1024, 256, 256, 4096)
             == [(0, 1024, 1024, 1024)]]
    assert whole == [1024, 2048, 3072]
    # phi4flash's window fits no tile between its diagonals
    assert not any(fa._tile_blocks(d, 1024, 1024, 256, 256, 512)
                   == [(0, 1024, 1024, 1024)] for d in range(0, 2048, 1024))


def test_windowed_grid_walks_only_the_band():
    """At the cell's shape (S 16384, window 512) a q tile walks 3 of
    its 32 kv tiles of 512 and a kv tile 3 of the 32 q tiles; the
    tile a step fetches stops at the last one that owes anything."""
    offs = jnp.zeros((2,), jnp.int32)
    steps, tile, fetch = fa._streamed_tiles(512, 512, 512, 32, True)
    assert steps == 3
    assert [int(tile(5, s, offs)) for s in range(3)] == [4, 5, 6]
    assert [int(fetch(5, s, offs)) for s in range(3)] == [4, 5, 5]
    assert [int(tile(0, s, offs)) for s in range(3)] == [0, 1, 2]
    assert [int(tile(31, s, offs)) for s in range(3)] == [29, 30, 31]
    steps, tile, fetch = fa._streamed_tiles(512, 512, 512, 32, False)
    assert steps == 3
    assert [int(tile(5, s, offs)) for s in range(3)] == [5, 6, 7]
    assert [int(fetch(5, s, offs)) for s in range(3)] == [5, 6, 6]
    # negative positions round down, not towards zero
    assert int(fa._floor_div(jnp.int32(-4), 4)) == -1
    assert int(fa._floor_div(jnp.int32(-5), 4)) == -2
    assert int(fa._floor_div(jnp.int32(7), 4)) == 1


# -- what a windowed call executes ---------------------------------------

def _brute_counts(seq_q, seq_k, block_q, block_k, sub, window, q_off,
                  k_off):
    """From the mask's own definition: a tile with no allowed score is
    skipped, one with all of them computed unmasked; a crossed tile on
    the tiles' grid is computed by row chunk over the sub-tiles the
    chunk sees, all of them masked unless the chunk sees them whole;
    off the grid the whole tile is computed masked."""
    sub_q, sub_k = sub
    ahead = (q_off + np.arange(seq_q))[:, None] \
        - (k_off + np.arange(seq_k))[None, :]
    allowed = (ahead >= 0) & (ahead < window)
    step = math.gcd(block_q, block_k)
    computed = masked = 0
    for q0 in range(0, seq_q, block_q):
        for k0 in range(0, seq_k, block_k):
            tile = allowed[q0:q0 + block_q, k0:k0 + block_k]
            subs = tile.reshape(block_q // sub_q, sub_q,
                                block_k // sub_k, sub_k)
            some, every = subs.any(axis=(1, 3)), subs.all(axis=(1, 3))
            d = (q_off + q0) - (k_off + k0)
            if not tile.any():
                continue
            if tile.all():
                computed += some.size
            elif d % step == 0 and fa._window_positions(
                    block_q, block_k, window):
                computed += int(some.sum())
                for chunk_some, chunk_every in zip(some, every):
                    if not chunk_every[chunk_some].all():
                        masked += int(chunk_some.sum())
            else:
                computed += some.size
                masked += some.size
    return {"computed": computed, "masked": masked,
            "skipped": (seq_q // sub_q) * (seq_k // sub_k) - computed}


# (id, seq_q, seq_k, block_q, block_k, sub, window, q_off, k_off)
_COUNT_CASES = [
    ("phi4flash-tiles", 2048, 2048, 1024, 1024, (256, 256), 512, 0, 0),
    ("long-tiles", 4096, 4096, 512, 1024, (256, 256), 512, 0, 0),
    ("wide-window", 4096, 4096, 512, 1024, (256, 256), 2048, 0, 0),
    ("small", 512, 512, 128, 256, (64, 64), 96, 0, 0),
    ("thin-window", 512, 512, 128, 128, (64, 32), 16, 0, 0),
    ("off-the-grid", 256, 512, 128, 256, (64, 64), 100, 37, 5),
    ("shard-behind", 256, 256, 128, 128, (64, 64), 160, 512, 256),
    ("shard-out-of-reach", 256, 256, 128, 128, (64, 64), 64, 1024, 256),
]
# counted against the mask, too long for the interpreter's kernels
_WIDE_COUNT_CASES = [
    ("smallthinker-tiles", 8192, 8192, 1024, 1024, (256, 256), 4096, 0, 0),
]
_COUNT_ARGS = "seq_q,seq_k,block_q,block_k,sub,window,q_off,k_off"


@pytest.mark.parametrize(
    _COUNT_ARGS, [c[1:] for c in _COUNT_CASES + _WIDE_COUNT_CASES],
    ids=[c[0] for c in _COUNT_CASES + _WIDE_COUNT_CASES])
def test_window_counts_match_the_mask(seq_q, seq_k, block_q, block_k, sub,
                                      window, q_off, k_off):
    assert fa.causal_subtile_counts(
        seq_q, seq_k, block_q, block_k, sub, q_off, k_off, window) \
        == _brute_counts(seq_q, seq_k, block_q, block_k, sub, window,
                         q_off, k_off)


def test_window_executes_a_band_not_a_triangle():
    """At the cell's shape the windowed call computes 3 sub-tiles a row
    chunk (a band of 512 keys over chunks of 256 rows) where the causal
    call computes half the square."""
    n = fa.causal_subtile_counts(16384, 16384, 512, 512, (256, 256),
                                 window=512)
    assert n["computed"] == 64 * 3 - 3          # the first chunks see less
    full = fa.causal_subtile_counts(16384, 16384, 512, 1024, (256, 256))
    assert full["computed"] > 10 * n["computed"]


@pytest.mark.parametrize(_COUNT_ARGS, [c[1:] for c in _COUNT_CASES[3:]],
                         ids=[c[0] for c in _COUNT_CASES[3:]])
def test_windowed_kernels_compute_what_the_counts_say(
        monkeypatch, seq_q, seq_k, block_q, block_k, sub, window, q_off,
        k_off):
    """The blocks each of the three kernels really forms, on traced
    offsets and over two query heads that share a key-value head, add
    up to ``causal_subtile_counts`` a query head."""
    seen = []
    _counted(monkeypatch, fa, "_attend", seen)
    _counted(monkeypatch, fa, "_recompute_p_ds", seen)
    q = jnp.ones((2, seq_q, 16), jnp.float32)
    kv = jnp.ones((1, seq_k, 16), jnp.float32)
    stat = jnp.ones((2, 1, seq_q), jnp.float32)
    args = dict(causal=True, block_q=block_q, block_k=block_k,
                interpret=True, sub=sub, window=window)
    fwd = jax.jit(lambda o: fa._flash_bhsd.__wrapped__(q, kv, kv, o, **args))
    dq = jax.jit(lambda o: fa._flash_bwd_bhsd.__wrapped__(
        q, kv, kv, q, stat, stat, o, **args)[0])
    dkv = jax.jit(lambda o: fa._flash_bwd_bhsd.__wrapped__(
        q, kv, kv, q, stat, stat, o, **args)[1:])
    want = fa.causal_subtile_counts(seq_q, seq_k, block_q, block_k, sub,
                                    q_off, k_off, window)
    for kernel in (fwd, dq, dkv):
        del seen[:]
        jax.block_until_ready(kernel(jnp.asarray([q_off, k_off], jnp.int32)))
        jax.effects_barrier()
        assert sum((r // sub[0]) * (c // sub[1]) for r, c, _ in seen) \
            == 2 * want["computed"]
        assert sum((r // sub[0]) * (m // sub[1]) for r, _, m in seen) \
            == 2 * want["masked"]


def test_the_output_leaves_the_accumulator_unrounded_when_asked():
    """bfloat16 operands, ``out_dtype`` float32: the kernel writes what
    it accumulated, and the backward takes a float32 cotangent."""
    q, k, v, g = (x.astype(jnp.bfloat16) for x in
                  _case(9, 1, 64, 64, 2, 1, 16, 32))

    def flash(q, k, v, out_dtype):
        return fa.flash_attention(q, k, v, window=24, block_q=32,
                                  block_k=32, interpret=True,
                                  out_dtype=out_dtype)

    g = g.astype(jnp.float32)
    exact, grads = out_and_vjp(lambda *x: flash(*x, jnp.float32), g, q, k, v)
    rounded = flash(q, k, v, None)
    assert exact.dtype == jnp.float32 and rounded.dtype == jnp.bfloat16
    np.testing.assert_array_equal(exact.astype(jnp.bfloat16), rounded)
    assert float(jnp.abs(exact - rounded.astype(jnp.float32)).max()) > 0
    want, grads_want = out_and_vjp(
        lambda *x: fa._dense_reference(*x, True, 0, 0, 24, jnp.float32),
        g, *(x.astype(jnp.float32) for x in (q, k, v)))
    np.testing.assert_allclose(exact, want, atol=2e-2)
    for got, ref in zip(grads, grads_want):
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(got.astype(jnp.float32), ref, atol=0.15)
