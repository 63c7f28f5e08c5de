"""The delta rules' prologue (``horovod_tpu/parallel/qkv_prologue.py``:
the causal taps, SiLU, the L2 norm a head, the scale and the cast in
one kernel pass each way) in interpreter mode against the composition
it replaced, written out here as the models ran it
(``CausalDepthwiseConv`` -> ``silu`` -> the norm -> the scale -> the
cast): forward, ``dx`` and ``dkernel`` at Kimi delta attention's shape
and the Gated DeltaNet's (``x`` the leading columns of a wider array),
over several tiles with the rows beside every edge compared by
themselves, at a length that is no multiple of the tile, on two
sequences, in float32 and in bfloat16, on operands with rows of zeros
and near-zeros, rows where SiLU saturates and a channel whose taps
cancel. Then the two mixers against their former selves, the two
models' parameter trees, and what a recomputed block keeps. (Cold on
this sandbox: 45 s.)"""

import hashlib
import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .chip_bench import _paths  # noqa: F401  (makes chipbench importable)
from .compiled import out_and_vjp
from .former_mixers import (
    MIXERS, a_mixer_is_its_former_self_in_bfloat16,
    a_recomputed_blocks_backward, l2_normalised)
from chipbench import harness, weights

from horovod_tpu.models.phi4flash import CausalDepthwiseConv
from horovod_tpu.parallel import qkv_prologue as qp
from horovod_tpu.parallel.gated_delta import lay_heads, take_heads

pytestmark = [pytest.mark.fast, pytest.mark.time_limit(120),
              pytest.mark.interpreter_of_its_own]

TAPS, EPS = 4, 1e-6


def the_chain_it_replaced(x, kernel, dim, hq, hk, hv):
    """q, k, v [B, S, H x D] in ``x``'s type as the two mixers made
    them before: the leading columns of ``x`` through the convolution
    in float32 and SiLU, q and k normalised a head, q scaled."""
    keys_q, keys_k, width = hq * dim, (hq + hk) * dim, (hq + hk + hv) * dim
    lead = x.shape[:2]
    qkv = nn.silu(CausalDepthwiseConv(TAPS, use_bias=False).apply(
        {"params": {"kernel": kernel}}, x[..., :width]))
    q = l2_normalised(qkv[..., :keys_q].reshape(*lead, hq, dim)) * dim ** -0.5
    k = l2_normalised(qkv[..., keys_q:keys_k].reshape(*lead, hk, dim))
    v = qkv[..., keys_k:]
    return (q.reshape(*lead, -1).astype(x.dtype),
            k.reshape(*lead, -1).astype(x.dtype), v.astype(x.dtype))


def operands(seed, batch, seq, dim, hq, hk, hv, extra, dtype):
    """What the projection hands over, with the model's hard rows
    planted: five rows of zeros (the pre-activation of the last is zero
    in every channel: the norm is ``0 rsqrt(eps)``), a row of 1e-4 (the
    sum of squares is of ``eps``'s size), rows of +-30 (SiLU is the
    identity or nothing), and a channel a head whose taps cancel on a
    constant input."""
    width = (hq + hk + hv) * dim
    kx, kw, *kc = jax.random.split(jax.random.key(seed), 5)
    x = 1.5 * jax.random.normal(kx, (batch, seq, width + extra))
    x = x.at[:, 4:9].set(0.0).at[:, 10].multiply(1e-4)
    x = x.at[:, 12].set(30.0).at[:, 13].set(-30.0)
    x = x.at[:, :, 3::dim].set(0.75)
    kernel = 0.5 * jax.random.normal(kw, (TAPS, width))
    kernel = kernel.at[:, 3::dim].set(
        jnp.array([1.0, -1.0, 1.0, -1.0])[:, None])
    cot = tuple(jax.random.normal(k, (batch, seq, h * dim)).astype(dtype)
                for k, h in zip(kc, (hq, hk, hv)))
    return x.astype(dtype), kernel, cot


# (case, batch, sequence, rows a tile, chunk, D, q, k and v heads, columns
#  of x past the prologue's, type)
CASES = [
    ("kimi_delta_attention", 1, 48, 16, 16, 128, 2, 2, 2, 0, jnp.float32),
    ("the_gated_deltanet_in_a_wider_array", 1, 48, 32, 16, 128, 1, 1, 2, 256,
     jnp.float32),
    ("four_tiles_of_two_chunks", 1, 128, 32, 16, 16, 2, 2, 2, 0, jnp.float32),
    ("a_ragged_tail", 1, 72, 32, 32, 16, 2, 2, 4, 32, jnp.float32),
    ("two_sequences", 2, 64, 16, 16, 16, 2, 2, 2, 0, jnp.float32),
    ("shorter_than_a_tile", 2, 21, None, None, 8, 2, 2, 2, 0, jnp.float32),
    ("bfloat16_as_the_models_run_it", 2, 80, 32, 16, 128, 2, 2, 2, 0,
     jnp.bfloat16),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_the_kernels_are_the_chain_they_replaced(case):
    _, batch, seq, rows, chunk, dim, hq, hk, hv, extra, dtype = case
    x, kernel, cot = operands(7, batch, seq, dim, hq, hk, hv, extra, dtype)
    got, (got_dx, got_dw) = out_and_vjp(
        lambda x, w: qp.qkv_prologue(x, w, dim, hq + hk, hq, rows=rows,
                                     chunk=chunk, interpret=True),
        cot, x, kernel)
    want, (want_dx, want_dw) = out_and_vjp(
        lambda x, w: the_chain_it_replaced(x, w, dim, hq, hk, hv),
        cot, x, kernel)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    # bfloat16: an ulp of the output; the old backward rounded each
    # tap's gradient before adding the four, the kernel rounds once
    out_tol, dx_tol = (dict(rtol=2e-5, atol=1e-6), dict(rtol=2e-4)) \
        if dtype == jnp.float32 else (dict(rtol=2 ** -7, atol=1e-6),
                                      dict(rtol=2 ** -5))
    dx_tol["atol"] = (1e-5 if dtype == jnp.float32 else 2 ** -7) \
        * float(np.abs(f32(want_dx)).max())
    rows = rows or seq
    edges = [(lo, hi) for lo, hi in (
        (max(at - TAPS, 0), min(at + TAPS, seq))
        for at in range(0, seq + 1, rows)) if lo < hi]
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape and g.dtype == w.dtype == dtype, name
        assert np.all(np.isfinite(f32(g))), name
        np.testing.assert_allclose(f32(g), f32(w), err_msg=name, **out_tol)
    assert got_dx.shape == x.shape and got_dx.dtype == dtype
    assert got_dw.shape == kernel.shape and got_dw.dtype == jnp.float32
    assert not np.any(f32(got_dx)[..., kernel.shape[1]:])   # z's columns
    for lo, hi in edges:        # a tile's edge: both halos are crossed
        np.testing.assert_allclose(
            f32(got_dx)[:, lo:hi], f32(want_dx)[:, lo:hi],
            err_msg=f"dx, rows {lo} to {hi}", **dx_tol)
    np.testing.assert_allclose(f32(got_dx), f32(want_dx), err_msg="dx",
                               **dx_tol)
    np.testing.assert_allclose(
        got_dw, want_dw, rtol=2e-4 if dtype == jnp.float32 else 2 ** -6,
        atol=(1e-5 if dtype == jnp.float32 else 2 ** -8)
        * float(np.abs(want_dw).max()), err_msg="dkernel")
    assert np.abs(f32(want[0])[:, 8]).max() == 0    # the zero rows' q


# (case, batch, sequence, rows a tile, key heads of ``dim`` over as many
#  value heads of twice that, the lanes a run of ``dim`` is laid out to,
#  columns of x past the prologue's, type)
LAID_OUT = [
    ("a_key_head_of_12_over_a_value_head_of_24", 2, 40, 16, 12, 2, 16, 0,
     jnp.float32),
    ("in_a_wider_array_in_bfloat16", 1, 48, 32, 24, 2, 128, 96,
     jnp.bfloat16),
]


@pytest.mark.parametrize("case", LAID_OUT, ids=[c[0] for c in LAID_OUT])
def test_heads_off_the_lane_tile_come_and_leave_laid_out(case):
    """Olmo-Hybrid's ratio, a key head of ``dim`` over one value head of
    ``2 dim`` with ``dim`` no whole lanes: ``x`` and the kernel laid out
    a run of ``dim`` columns at a time, ``key_dim`` for q's scale. Taken
    back, q, k, v, ``dx`` and ``dkernel`` are the chain's on the columns
    as they came; the lanes between stay zero."""
    _, batch, seq, rows, dim, heads, lane, extra, dtype = case
    hv = 2 * heads                      # a value head is two runs of dim
    x, kernel, cot = operands(7, batch, seq, dim, heads, heads, hv, extra,
                              dtype)
    laid = -(-dim // lane) * lane
    lay = lambda a: lay_heads(a, dim, lane)
    take = lambda a: take_heads(a, dim, lane)

    def laid_out(x, w):
        out = qp.qkv_prologue(lay(x), lay(w), laid, 2 * heads, heads,
                              rows=rows, interpret=True, key_dim=dim)
        return out, tuple(take(o) for o in out)

    (got_laid, got), (got_dx, got_dw) = out_and_vjp(
        laid_out, (tuple(jnp.zeros_like(lay(c)) for c in cot), cot), x,
        kernel)
    want, (want_dx, want_dw) = out_and_vjp(
        lambda x, w: the_chain_it_replaced(x, w, dim, heads, heads, hv),
        cot, x, kernel)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    ulp = 2e-5 if dtype == jnp.float32 else 2 ** -7
    for name, g, w, l in zip("qkv", got, want, got_laid):
        assert g.shape == w.shape and g.dtype == dtype, name
        np.testing.assert_allclose(f32(g), f32(w), rtol=ulp, atol=1e-6,
                                   err_msg=name)
        assert not np.any(f32(l).reshape(*l.shape[:2], -1, laid)[..., dim:])
    assert got_dx.shape == x.shape and got_dw.shape == kernel.shape
    scale = float(np.abs(f32(want_dx)).max())
    np.testing.assert_allclose(
        f32(got_dx), f32(want_dx), err_msg="dx",
        rtol=2e-4 if dtype == jnp.float32 else 2 ** -5,
        atol=(1e-5 if dtype == jnp.float32 else 2 ** -7) * scale)
    np.testing.assert_allclose(
        got_dw, want_dw, err_msg="dkernel",
        rtol=2e-4 if dtype == jnp.float32 else 2 ** -6,
        atol=(1e-5 if dtype == jnp.float32 else 2 ** -8)
        * float(np.abs(want_dw).max()))


def test_what_the_kernels_cannot_tile_is_refused():
    x, kernel, _ = operands(1, 1, 32, 16, 2, 2, 2, 0, jnp.float32)
    run = lambda **kw: qp.qkv_prologue(x, kernel, 16, 4, 2, interpret=True,
                                       **kw)
    with pytest.raises(ValueError, match="multiples of 16"):
        run(rows=24)
    with pytest.raises(ValueError, match="the chunk dividing the tile"):
        run(rows=32, chunk=48)
    with pytest.raises(ValueError, match="heads a pass dividing"):
        run(group=4)
    with pytest.raises(ValueError, match="whole heads"):
        qp.qkv_prologue(x, kernel, 20, 4, 2, interpret=True)
    with pytest.raises(ValueError, match="scaled <= normalised"):
        qp.qkv_prologue(x, kernel, 16, 2, 4, interpret=True)
    with pytest.raises(ValueError, match=r"\[B,S,>=C\]"):
        qp.qkv_prologue(x[..., :64], kernel, 16, 4, 2, interpret=True)


def test_the_traced_call_leaves_its_tile_in_the_gauge(monkeypatch):
    from horovod_tpu.common import basics
    noted = {}
    monkeypatch.setattr(basics, "note_traced", lambda name, what, kinds:
                        noted.update({name: kinds}))
    x, kernel, _ = operands(1, 1, 48, 16, 2, 2, 4, 32, jnp.float32)
    qp.qkv_prologue(x, kernel, 16, 4, 2, rows=32, chunk=16)
    assert noted == {"hvd_qkv_prologue": {
        "tile_rows": 32, "tile_columns": 128, "normalised_heads": 4,
        "taps": 4}}


# -- the two mixers against their former selves -------------------------------

@pytest.mark.parametrize("name", list(MIXERS))
def test_a_mixer_is_its_former_self_in_bfloat16(name):
    """``tests/former_mixers.py``: against the mixer as it was before
    the prologue (PR 41 and PR 33), leaf for leaf."""
    p = a_mixer_is_its_former_self_in_bfloat16(name, "prologue")
    assert p["conv"]["kernel"].dtype == jnp.float32
    assert p["conv"]["kernel"].shape[0] == TAPS


# The whole models' trees at the rehearsal sizes, by the hash the
# parent commit gives (paths and shapes; the leaves are float32).
MODEL_TREES = {"ling3flash-injit-1chip": "c4768108670e4e44",
               "qwen3next-injit-1chip": "7f7d11904d27bce3"}


@pytest.mark.parametrize("cell", list(MODEL_TREES))
def test_the_two_models_parameter_trees_are_what_they_were(cell):
    spec = harness.resolve_cell(_paths.manifest(), cell, rehearse=True)
    family = harness.load_module("families", spec["config"]["family"])
    sz = family.sizes(spec["config"],
                      spec["config"]["assumed"]["per_chip_batch"])
    shapes = family.program_shapes(family.build_model(sz), sz)
    assert {a.dtype for a in jax.tree_util.tree_leaves(shapes["params"])} \
        == {jnp.dtype(jnp.float32)}
    flat = weights.flat_shapes(jax.tree_util.tree_map(
        lambda a: tuple(a.shape), shapes))
    convs = {p: s for p, s in flat.items() if "/mixer/conv/" in p}
    assert convs and all(p.endswith("/mixer/conv/kernel") and s[0] == TAPS
                         for p, s in convs.items())
    tree = json.dumps(flat, sort_keys=True)
    assert hashlib.sha256(tree.encode()).hexdigest()[:16] \
        == MODEL_TREES[cell]


# -- what a recomputed block keeps ------------------------------------------

def test_a_recomputed_block_makes_the_prologues_outputs_again():
    """``_keep_kernel_outputs`` keeps the rule's output, entering
    states and chunks' inverses and not the prologue's q, k, v (403 MB
    a layer at the cell's size): the backward of a recomputed block
    runs the prologue's forward kernel again and the rule's forward
    kernel not."""
    calls, kept = a_recomputed_blocks_backward("kimi_delta_attention")
    assert calls("qkv_prologue_fwd") == 2 and calls("qkv_prologue_bwd") == 1
    assert calls("kda_fwd") == 1 and calls("kda_bwd") == 1
    assert len(kept) == 3 and all("kimi_delta_attention" in k for k in kept)
    assert not any("qkv_prologue" in k for k in kept)
