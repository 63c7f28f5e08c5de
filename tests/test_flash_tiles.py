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

from .compiled import out_and_vjp

pytestmark = pytest.mark.interpreter_of_its_own


def _blocks_for(seq_q, seq_k, head_dim):
    ql, kl = _ladders_for(head_dim)
    return _auto_block(seq_q, ql, None), _auto_block(seq_k, kl, None)


def test_default_ladder_unchanged_up_to_256():
    """D <= 256 keeps the measured 512x1024 defaults exactly — the
    ladder change must not perturb validated configurations."""
    for d in (96, 128, 192, 256):
        assert _ladders_for(d) == (_BLOCK_Q_LADDER, _BLOCK_K_LADDER)
    assert _blocks_for(2048, 2048, 128) == (512, 1024)
    assert _blocks_for(512, 1024, 64) == (512, 1024)


def test_a_head_of_64_takes_a_q_tile_of_1024():
    """PR 31's sweep at head size 64 (20 over 10 heads, S 16384): the
    1024x1024 tile is 13% faster than 512x1024, causal and windowed."""
    for d in (32, 64):
        assert _ladders_for(d) == ((1024,) + _BLOCK_Q_LADDER,
                                   _BLOCK_K_LADDER)
    assert _blocks_for(16384, 16384, 64) == (1024, 1024)
    assert _blocks_for(1536, 1536, 64) == (512, 512)


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


# -- the causal sub-tile (PR 30) -------------------------------------------
#
# A tile the diagonal crosses is computed by sub-tile: wholly above the
# diagonal -> not computed, wholly at or under it -> computed without
# the mask, crossed by it -> masked. The tiles stay 512x1024; which
# blocks a tile owes is chosen from the runtime offsets among static
# bodies (`_over_tile`), `_tile_blocks` is the same rule on integers
# and `causal_subtile_counts` its sum over a call.

def _brute_counts(seq_q, seq_k, block_q, block_k, sub, q_off, k_off):
    """The counts from the mask's own definition, element by element:
    a tile with no allowed score is skipped, one with all of them is
    computed unmasked, one the diagonal crosses is computed by sub-tile
    if it sits on the grid of the tiles (and the tiles are no further
    than four such crossings apart) and whole, masked, if not."""
    import math
    sub_q, sub_k = sub
    allowed = (q_off + np.arange(seq_q))[:, None] \
        >= (k_off + np.arange(seq_k))[None, :]
    computed = masked = 0
    step = math.gcd(block_q, block_k)   # the grid of the tiles; at most
    # four crossings on it have bodies of their own
    for q0 in range(0, seq_q, block_q):
        for k0 in range(0, seq_k, block_k):
            tile = allowed[q0:q0 + block_q, k0:k0 + block_k]
            subs = tile.reshape(block_q // sub_q, sub_q,
                                block_k // sub_k, sub_k)
            some, every = subs.any(axis=(1, 3)), subs.all(axis=(1, 3))
            d = (q_off + q0) - (k_off + k0)
            if tile.all() or not tile.any():
                computed += int(some.sum())
            elif d % step == 0 and (block_q + block_k) // step <= 5:
                computed += int(some.sum())
                masked += int((some & ~every).sum())
            else:
                computed += some.size
                masked += some.size
    return {"computed": computed, "masked": masked,
            "skipped": (seq_q // sub_q) * (seq_k // sub_k) - computed}


# (id, seq_q, seq_k, block_q, block_k, sub, q_offset, k_offset)
_COUNT_CASES = [
    ("lm-cell", 2048, 2048, 512, 1024, (256, 256), 0, 0),
    ("glm-cell", 4096, 4096, 512, 1024, (256, 256), 0, 0),
    ("whole-tile", 2048, 2048, 512, 1024, (512, 1024), 0, 0),
    ("kv-halves", 4096, 4096, 512, 1024, (512, 512), 0, 0),
    ("tall-sub", 1024, 1024, 512, 512, (256, 128), 0, 0),
    ("wide-sub", 1024, 1024, 512, 512, (128, 256), 0, 0),
    ("wide-tile", 1024, 1024, 1024, 512, (256, 256), 0, 0),
    ("narrow-tile", 1024, 1024, 128, 1024, (128, 256), 0, 0),
    ("off-the-grid", 512, 1024, 256, 512, (128, 128), 37, 5),
    ("q-behind-k", 512, 512, 256, 256, (64, 128), 256, 512),
    ("shard-past", 512, 512, 512, 512, (256, 256), 1024, 512),
    ("shard-future", 512, 512, 512, 512, (256, 256), 512, 1024),
    ("shard-diagonal", 512, 512, 512, 512, (256, 256), 1024, 1024),
    ("shard-touching", 512, 512, 512, 512, (256, 256), 511, 1022),
]
_COUNT_ARGS = "seq_q,seq_k,block_q,block_k,sub,q_off,k_off"


@pytest.mark.parametrize(_COUNT_ARGS, [c[1:] for c in _COUNT_CASES],
                         ids=[c[0] for c in _COUNT_CASES])
def test_subtile_counts_match_the_mask(seq_q, seq_k, block_q, block_k,
                                       sub, q_off, k_off):
    from horovod_tpu.parallel.flash_attention import causal_subtile_counts
    assert causal_subtile_counts(
        seq_q, seq_k, block_q, block_k, sub, q_off, k_off) \
        == _brute_counts(seq_q, seq_k, block_q, block_k, sub, q_off, k_off)


def _counted(monkeypatch, fa, name, seen):
    """Have `fa.<name>` (the one place a kernel forms a block of scores)
    report each block it forms at run time: interpret mode runs only the
    bodies whose `pl.when` holds."""
    import jax
    inner = getattr(fa, name)

    def counting(*args):
        if name == "_attend":
            q, k, mask = args[0], args[1], args[-1]
            rows, cols = q.shape[0], k.shape[0]
            masked = 0 if mask is None else mask.shape[1]
        else:
            r0, r1, free, vis = args[6][:4]
            rows, cols, masked = r1 - r0, vis - fa._block_lo(args[6]), \
                vis - free
        jax.debug.callback(lambda: seen.append((rows, cols, masked)))
        return inner(*args)

    monkeypatch.setattr(fa, name, counting)


@pytest.mark.parametrize(_COUNT_ARGS, [c[1:] for c in _COUNT_CASES[4:]],
                         ids=[c[0] for c in _COUNT_CASES[4:]])
def test_kernels_compute_what_the_counts_say(monkeypatch, seq_q, seq_k,
                                             block_q, block_k, sub, q_off,
                                             k_off):
    """The blocks of scores each of the three kernels really forms, on
    **traced** offsets, add up to `causal_subtile_counts`."""
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    from horovod_tpu.parallel import flash_attention as fa
    seen = []
    _counted(monkeypatch, fa, "_attend", seen)
    _counted(monkeypatch, fa, "_recompute_p_ds", seen)
    qkv = jnp.ones((1, seq_q, 16), jnp.float32)
    kv = jnp.ones((1, seq_k, 16), jnp.float32)
    stat = jnp.ones((1, 1, seq_q), jnp.float32)
    args = dict(causal=True, block_q=block_q, block_k=block_k,
                interpret=True, sub=sub)
    fwd = jax.jit(lambda o: fa._flash_bhsd.__wrapped__(
        qkv, kv, kv, o, **args))
    dq = jax.jit(lambda o: fa._flash_bwd_bhsd.__wrapped__(
        qkv, kv, kv, qkv, stat, stat, o, **args)[0])
    dkv = jax.jit(lambda o: fa._flash_bwd_bhsd.__wrapped__(
        qkv, kv, kv, qkv, stat, stat, o, **args)[1:])
    want = fa.causal_subtile_counts(seq_q, seq_k, block_q, block_k, sub,
                                    q_off, k_off)
    for kernel in (fwd, dq, dkv):
        del seen[:]
        jax.block_until_ready(kernel(jnp.asarray([q_off, k_off],
                                                 jnp.int32)))
        jax.effects_barrier()
        assert sum((r // sub[0]) * (c // sub[1]) for r, c, _ in seen) \
            == want["computed"]
        assert sum((r // sub[0]) * (m // sub[1]) for r, _, m in seen) \
            == want["masked"]


@pytest.mark.parametrize("seq,head_dim,most,before", [
    (2048, 128, 0.625, 0.75),      # lm-injit-1chip, lm-injit-4chip
    (4096, 256, 0.5625, 0.625),    # glm47flash-injit-1chip
], ids=["pythia-s2048-d128", "glm-s4096-d256"])
def test_computed_share_at_the_cells_shapes(seq, head_dim, most, before):
    """The 'executed over counted' column of ISSUE 30 as a number: with
    the ladder's sub-tile the kernels compute at most this share of the
    square (the causal need is a half), where the whole-tile predicate
    computed `before`."""
    from horovod_tpu.parallel.flash_attention import (
        _subtile_for, causal_subtile_counts)
    bq, bk = _blocks_for(seq, seq, head_dim)
    assert (bq, bk) == (512, 1024)
    sub = _subtile_for(head_dim, bq, bk)
    assert sub != (bq, bk)

    def share(sub):
        n = causal_subtile_counts(seq, seq, bq, bk, sub)
        return n["computed"] / (n["computed"] + n["skipped"])

    assert share((bq, bk)) == before
    assert 0.5 < share(sub) <= most


def test_subtile_is_cut_to_the_tile():
    from horovod_tpu.parallel.flash_attention import _subtile_for
    sq, sk = _subtile_for(128, 512, 1024)
    assert 512 % sq == 0 and 1024 % sk == 0
    assert _subtile_for(128, 32, 32) == (32, 32)        # the tests' tiles
    assert _subtile_for(64, 96, 1024)[0] == 96          # no divisor: whole
    assert _subtile_for(4096, 128, 128) == (128, 128)   # past the ladder


def test_diagonal_blocks_at_the_cells_tile():
    """512x1024 tiles in self-attention meet the diagonal at two
    positions; each has its body, and a 256x256 sub-tile leaves 3 of 8
    and 7 of 8 sub-tiles of such a tile, two of them masked."""
    from horovod_tpu.parallel.flash_attention import (
        _diagonal_blocks, _diagonal_positions, _tile_blocks)
    assert _diagonal_positions(512, 1024) == (0, 512)
    assert _diagonal_positions(512, 512) == (0,)
    assert _diagonal_positions(1024, 512) == (-512, 0)
    # (row_lo, row_hi, free, vis): columns [0, free) unmasked, then
    # [free, vis) masked
    assert _diagonal_blocks(0, 512, 1024, 256, 256) == [
        (0, 256, 0, 256), (256, 512, 256, 512)]
    assert _diagonal_blocks(512, 512, 1024, 256, 256) == [
        (0, 256, 512, 768), (256, 512, 768, 1024)]
    assert _tile_blocks(-512, 512, 1024, 256, 256) == []
    assert _tile_blocks(1023, 512, 1024, 256, 256) == [(0, 512, 1024, 1024)]
    assert _tile_blocks(1022, 512, 1024, 256, 256) == [(0, 512, 0, 1024)]
    assert _tile_blocks(-511, 512, 1024, 256, 256) == [(0, 512, 0, 1024)]


def _kernel_case(seq_q, seq_k, d, seed):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(1, s, 1, d), jnp.float32)
            for s in (seq_q, seq_k, seq_k, seq_q)]


# (id, seq_q, seq_k, block_q, block_k, sub, [(q_offset, k_offset), ...]):
# every list of offsets runs through ONE compilation of each kernel.
_KERNEL_CASES = [
    # 128x256 tiles of 64x64 sub-tiles: the diagonal crosses one
    # sub-tile a row chunk at both of the tile's positions; then offsets
    # that are no multiple of the sub-tile, nor of the tile
    ("crosses-one", 256, 256, 128, 256, (64, 64),
     [(0, 0), (128, 0), (64, 0), (37, 5), (3, 70)]),
    # a tall sub-tile over narrow columns: several crossed a row chunk
    ("crosses-several", 256, 256, 128, 256, (128, 32), [(0, 0), (0, 19)]),
    # a wide sub-tile: the crossed one starts off the row chunk's grid
    ("wide-sub", 256, 256, 128, 256, (32, 128), [(0, 0), (128, 128)]),
    # a q tile wider than the kv tile meets the diagonal from above
    ("wide-tile", 512, 512, 256, 128, (64, 64), [(0, 0), (128, 0)]),
    # the ring's shards, square tiles: wholly past (no sub-tile masked),
    # wholly future (none computed), touching in one corner element
    ("ring-shards", 128, 128, 128, 128, (64, 64),
     [(256, 128), (128, 256), (127, 254), (128, 128)]),
    # the ladder's own sub-tile at the cells' tile
    ("ladder-512x1024", 1024, 1024, 512, 1024, None, [(0, 0)]),
]


def _jitted_kernels(qb, kb, vb, dob, block_q, block_k, sub):
    """The causal forward and the three gradients of one tile choice as
    one program of the offsets, interpret mode: ``(o, dq, dk, dv)``."""
    import jax
    from horovod_tpu.parallel import flash_attention as fa

    @jax.jit
    def kernels(offs):
        o, m, l = fa._flash_bhsd(qb, kb, vb, offs, True, block_q, block_k,
                                 True, sub)
        lse = fa._lse_from_stats(m[:, 0][None], l[:, 0][None])
        delta = jax.numpy.sum(dob * o, axis=-1)[:, None, :]
        return (o,) + fa._flash_bwd_bhsd(qb, kb, vb, dob, lse, delta, offs,
                                         True, block_q, block_k, True, sub)

    return kernels


@pytest.mark.parametrize("seq_q,seq_k,block_q,block_k,sub,offsets",
                         [c[1:] for c in _KERNEL_CASES],
                         ids=[c[0] for c in _KERNEL_CASES])
def test_subtiled_kernels_match_dense(seq_q, seq_k, block_q, block_k, sub,
                                      offsets):
    """Forward with its statistics and the three gradients against
    `_dense_reference`, interpret mode, traced offsets under one jit."""
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    from horovod_tpu.parallel import flash_attention as fa
    d = 16
    q, k, v, do = _kernel_case(seq_q, seq_k, d, seed=seq_q + block_q)
    qb, kb, vb, dob = (fa._to_bhsd(x) for x in (q, k, v, do))

    kernels = _jitted_kernels(qb, kb, vb, dob, block_q, block_k, sub)

    @jax.jit
    def reference(offs):
        ref, vjp = jax.vjp(
            lambda q, k, v: fa._dense_reference(q, k, v, True, offs[0],
                                                offs[1]), q, k, v)
        return (ref,) + vjp(do)

    for q_off, k_off in offsets:
        offs = jnp.asarray([q_off, k_off], jnp.int32)
        for name, a, b in zip(("o", "dq", "dk", "dv"), kernels(offs),
                              reference(offs)):
            np.testing.assert_allclose(
                np.asarray(fa._from_bhsd(a, 1, 1)), np.asarray(b),
                atol=2e-5, err_msg=f"{name} at offsets {(q_off, k_off)}")
    assert kernels._cache_size() == 1


def test_skipped_subtiles_are_never_touched():
    """kv columns in the future of every q row sit inside a tile that is
    computed (256 q rows against one 512-wide kv tile). NaNs there reach
    nothing: their sub-tiles are not computed, where a mask would have
    multiplied them by zero."""
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    from horovod_tpu.parallel import flash_attention as fa
    q, k, v, do = _kernel_case(256, 512, 16, seed=5)
    poison = jnp.arange(512)[None, :, None, None] >= 256
    k_bad, v_bad = (jnp.where(poison, jnp.nan, x) for x in (k, v))
    ref, (rq, rk, rv) = out_and_vjp(
        lambda q, k, v: fa._dense_reference(q, k, v, True, 0, 0),
        do, q, k[:, :256], v[:, :256])
    qb, kb, vb, dob = (fa._to_bhsd(x) for x in (q, k_bad, v_bad, do))

    o, dq, dk, dv = _jitted_kernels(qb, kb, vb, dob, 256, 512, (128, 128))(
        jnp.zeros((2,), jnp.int32))
    np.testing.assert_allclose(np.asarray(fa._from_bhsd(o, 1, 1)),
                               np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(fa._from_bhsd(dq, 1, 1)),
                               np.asarray(rq), atol=2e-5)
    for got, want in ((dk, rk), (dv, rv)):
        got = np.asarray(fa._from_bhsd(got, 1, 1))
        np.testing.assert_allclose(got[:, :256], np.asarray(want),
                                   atol=2e-5)
        assert not got[:, 256:].any()       # no q row sees these columns


def _kernel_primitives(fn, *args):
    """Names of the primitives inside the Pallas kernels `fn` calls,
    nested bodies (`pl.when`) included, one list a kernel."""
    import jax

    def walk(jaxpr, into):
        for eqn in jaxpr.eqns:
            into.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, into)

    kernels = []

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels.append([])
                walk(eqn.params["jaxpr"], kernels[-1])
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    find(sub)

    find(jax.make_jaxpr(fn)(*args).jaxpr)
    return kernels


@pytest.mark.parametrize("causal", [False, True],
                         ids=["noncausal", "causal"])
def test_noncausal_body_has_no_loop_and_no_mask(causal):
    """`causal=False` lowers to the body it always had: one product
    pair a tile, no loop, no branch on the offsets, no iota, no compare.
    The causal kernels hold one body a case and mask only the blocks
    the diagonal crosses."""
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    from horovod_tpu.parallel import flash_attention as fa
    qkv = jnp.zeros((1, 256, 16), jnp.float32)
    stat = jnp.zeros((1, 1, 256), jnp.float32)
    offs = jnp.zeros((2,), jnp.int32)
    fwd, = _kernel_primitives(
        lambda *a: fa._flash_bhsd(*a, causal, 128, 256, True, (64, 64)),
        qkv, qkv, qkv, offs)
    dq, dkv = _kernel_primitives(
        lambda *a: fa._flash_bwd_bhsd(*a, causal, 128, 256, True,
                                      (64, 64)),
        qkv, qkv, qkv, qkv, stat, stat, offs)
    # 128x256 tiles of 64x64: one block under the diagonal; two row
    # chunks, each masked in its last sub-tile, at either position on
    # the grid; one masked block off it
    blocks, masked = 1 + 2 + 2 + 1, 2 + 2 + 1
    for prims, dots, first_last in ((fwd, 2, 2), (dq, 3, 2), (dkv, 4, 2)):
        assert not {"while", "scan"} & set(prims)
        if causal:
            assert prims.count("cond") == first_last + 4
            assert prims.count("dot_general") == dots * blocks
            assert prims.count("iota") == 2 * masked
        else:
            assert prims.count("cond") == first_last
            assert not {"iota", "ge"} & set(prims)
            assert prims.count("dot_general") == dots
    # the forward's one select without a mask is the guarded denominator
    assert fwd.count("select_n") == (1 + 2 * masked if causal else 1)
    assert dq.count("select_n") == dkv.count("select_n") \
        == (masked if causal else 0)
