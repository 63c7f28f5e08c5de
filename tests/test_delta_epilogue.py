"""The delta rules' epilogue (``horovod_tpu/parallel/delta_epilogue.py``:
the RMSNorm a head, its gate and the cast in one kernel pass each way)
in interpreter mode against the composition it replaced, written out
here as the models ran it (``nn.RMSNorm`` in float32 -> the gate -> the
cast): ``y``, ``do``, ``dz`` and ``dscale`` at Kimi delta attention's
shape (a head's float32 scalar under ``sigmoid``) and the Gated
DeltaNet's (``z`` the trailing columns of a wider bfloat16 array under
``silu``), over several tiles, at a length that is no multiple of the
tile, on two sequences, and on operands that resemble the models': rows
whose RMS spans five decades, a row of zeros, gates at -12 and +12.
Then the two mixers against their former selves, the two models'
parameter trees, and what a recomputed block keeps. (Cold on this
sandbox: 40 s.)"""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .chip_bench import _paths  # noqa: F401  (makes chipbench importable)
from .compiled import out_and_vjp
from .former_mixers import (
    MIXERS, a_mixer_is_its_former_self_in_bfloat16,
    a_recomputed_blocks_backward)
from chipbench import harness, weights

from horovod_tpu.models import glm_moe
from horovod_tpu.parallel import delta_epilogue as de
from horovod_tpu.parallel.gated_delta import lay_heads, take_heads

pytestmark = [pytest.mark.fast, pytest.mark.time_limit(120),
              pytest.mark.interpreter_of_its_own]

EPS = 1e-6
ACTIVATIONS = {"sigmoid": jax.nn.sigmoid, "silu": nn.silu}


def the_chain_it_replaced(o, scale, gate, dim, activation, start):
    """``y`` [B, S, H x D] in ``o``'s type as the two mixers made it
    before: ``nn.RMSNorm`` a head in float32, times the activation of a
    head's scalar or of an element's gate, the cast."""
    bt, seq, width = o.shape
    heads = width // dim
    n = nn.RMSNorm(epsilon=EPS, dtype=jnp.float32,
                   param_dtype=jnp.float32).apply(
        {"params": {"scale": scale}},
        o.astype(jnp.float32).reshape(bt, seq, heads, dim))
    act = ACTIVATIONS[activation]
    if gate.shape[2] == heads:
        y = n * act(gate)[..., None]
    else:
        y = n * act(gate[..., start:start + width].reshape(
            bt, seq, heads, dim).astype(jnp.float32))
    return y.astype(o.dtype).reshape(bt, seq, width)


def operands(seed, batch, seq, heads, dim, per_head, before, after, dtype,
             hard=False):
    """What the rule's kernel and the projections hand over. ``hard``
    plants the model's rows: an RMS from 1e-3 to 1e2 down the sequence
    (a delta rule's output grows with what its state has summed), a row
    of zeros (``eps`` alone holds the norm), and gates at -12 and +12
    (``sigmoid`` and ``silu`` at both ends)."""
    width = heads * dim
    ko, kw, kz, kc = jax.random.split(jax.random.key(seed), 4)
    o = jax.random.normal(ko, (batch, seq, width))
    gate = jax.random.normal(
        kz, (batch, seq, heads if per_head else before + width + after))
    if hard:
        o = o * jnp.logspace(-3, 2, seq)[None, :, None]
        o = o.at[:, 5].set(0.0)
        gate = 2.0 * gate
        gate = gate.at[:, 3].set(-12.0).at[:, 7].set(12.0)
    scale = 1.0 + 0.2 * jax.random.normal(kw, (dim,))
    cot = jax.random.normal(kc, (batch, seq, width)).astype(dtype)
    return (o.astype(dtype), scale,
            gate if per_head else gate.astype(dtype), cot)


# (case, batch, sequence, rows a tile, chunk, heads, D, a head's scalar,
#  activation, columns of the gate's array before and after z's, type,
#  the model's hard rows)
CASES = [
    ("kimi_delta_attention", 1, 32, 16, 16, 32, 128, True, "sigmoid", 0, 0,
     jnp.bfloat16, False),
    ("the_gated_deltanet_in_a_wider_array", 1, 48, 32, 16, 4, 128, False,
     "silu", 1024, 0, jnp.bfloat16, False),
    ("four_tiles_of_two_chunks", 1, 128, 32, 16, 4, 16, True, "sigmoid", 0,
     0, jnp.float32, False),
    ("a_ragged_tail_a_heads_gate", 1, 72, 32, 32, 4, 16, True, "sigmoid", 0,
     0, jnp.float32, False),
    ("a_ragged_tail_an_elements_gate", 1, 72, 32, 16, 4, 16, False, "silu",
     128, 64, jnp.float32, False),
    ("two_sequences_a_heads_gate", 2, 64, 16, 16, 4, 16, True, "sigmoid", 0,
     0, jnp.float32, False),
    ("two_sequences_an_elements_gate", 2, 64, 16, 16, 4, 16, False, "silu",
     64, 0, jnp.bfloat16, False),
    ("shorter_than_a_tile", 2, 21, None, None, 2, 8, True, "sigmoid", 0, 0,
     jnp.float32, False),
    ("the_models_rows_a_heads_gate", 2, 80, 32, 16, 4, 128, True, "sigmoid",
     0, 0, jnp.bfloat16, True),
    ("the_models_rows_an_elements_gate", 2, 80, 32, 16, 2, 128, False,
     "silu", 512, 0, jnp.bfloat16, True),
    ("silu_of_a_heads_gate", 1, 48, 16, 16, 4, 16, True, "silu", 0, 0,
     jnp.float32, True),
    ("sigmoid_of_an_elements_gate", 1, 48, 16, 16, 4, 16, False, "sigmoid",
     0, 0, jnp.float32, True),
]


# (case, batch, sequence, rows a tile, heads, a head's width (two runs
#  of half of it), the lanes a run is laid out to, columns of the gate's
#  array before z's in runs, type)
LAID_OUT = [
    ("a_head_of_24_as_two_runs_of_12", 2, 40, 16, 4, 24, 16, 0,
     jnp.float32),
    ("in_a_wider_array_in_bfloat16_the_models_rows", 1, 48, 32, 2, 48, 128,
     8, jnp.bfloat16),
]


@pytest.mark.parametrize("case", LAID_OUT, ids=[c[0] for c in LAID_OUT])
def test_heads_off_the_lane_tile_come_and_leave_laid_out(case):
    """Olmo-Hybrid's value head, two runs of a width that is no whole
    lanes: ``o``, the scale and an element's gate laid out a run at a
    time, ``filled`` the mean's divisor. Taken back, ``y``, ``do``,
    ``dz`` and ``dscale`` are the chain's on the columns as they came;
    the lanes between stay zero."""
    _, batch, seq, rows, heads, dim, lane, before, dtype = case
    run = dim // 2
    start = before * heads * dim        # whole widths of z before it
    o, scale, gate, cot = operands(13, batch, seq, heads, dim, False, start,
                                   0, dtype, hard=True)
    lay = lambda a: lay_heads(a, run, lane)
    take = lambda a: take_heads(a, run, lane)
    laid = 2 * (-(-run // lane) * lane)

    def laid_out(o, w, z):
        y = de.delta_epilogue(lay(o), lay(w), lay(z), laid, "silu",
                              lay(z[..., :start]).shape[2], EPS, rows=rows,
                              interpret=True, filled=dim)
        return y, take(y)

    (got_laid, got), (got_do, got_dw, got_dz) = out_and_vjp(
        laid_out, (jnp.zeros_like(lay(cot)), cot), o, scale, gate)
    want, (want_do, want_dw, want_dz) = out_and_vjp(
        lambda o, w, z: the_chain_it_replaced(o, w, z, dim, "silu", start),
        cot, o, scale, gate)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    ulp = 2e-5 if dtype == jnp.float32 else 2 ** -7
    close = lambda g, w, what, rtol=ulp, floor=1e-6: \
        np.testing.assert_allclose(
            f32(g), f32(w), rtol=rtol,
            atol=floor * float(np.abs(f32(w)).max()), err_msg=what)
    assert got.shape == o.shape and got.dtype == dtype
    close(got, want, "y")
    assert not np.any(f32(got_laid).reshape(
        batch, seq, -1, laid // 2)[..., run:])
    close(got_do, want_do, "do", floor=1e-5 if dtype == jnp.float32 else ulp)
    assert got_dz.shape == gate.shape
    close(got_dz, want_dz, "dz",
          floor=1e-5 if dtype == jnp.float32 else ulp)
    assert got_dw.shape == (dim,)
    close(got_dw, want_dw, "dscale", rtol=2e-4, floor=1e-5)


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_the_kernels_are_the_chain_they_replaced(case):
    (_, batch, seq, rows, chunk, heads, dim, per_head, activation, before,
     after, dtype, hard) = case
    o, scale, gate, cot = operands(11, batch, seq, heads, dim, per_head,
                                   before, after, dtype, hard)
    got, (got_do, got_dw, got_dz) = out_and_vjp(
        lambda o, w, z: de.delta_epilogue(
            o, w, z, dim, activation, before, EPS, rows=rows, chunk=chunk,
            interpret=True), cot, o, scale, gate)
    want, (want_do, want_dw, want_dz) = out_and_vjp(
        lambda o, w, z: the_chain_it_replaced(o, w, z, dim, activation,
                                              before), cot, o, scale, gate)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    # bfloat16: an ulp of what is rounded once on both sides
    ulp = 2e-5 if dtype == jnp.float32 else 2 ** -7
    close = lambda g, w, what, rtol=ulp, floor=1e-6: \
        np.testing.assert_allclose(
            f32(g), f32(w), rtol=rtol,
            atol=floor * float(np.abs(f32(w)).max()), err_msg=what)
    assert got.shape == o.shape and got.dtype == dtype
    assert np.all(np.isfinite(f32(got)))
    close(got, want, "y")
    assert got_do.shape == o.shape and got_do.dtype == dtype
    # do = r (g - xr mean(g xr)): two terms that cancel along o
    close(got_do, want_do, "do", floor=1e-5 if dtype == jnp.float32 else ulp)
    assert got_dz.shape == gate.shape and got_dz.dtype == gate.dtype
    assert np.all(np.isfinite(f32(got_dz)))
    width = heads * dim
    if not per_head:        # the columns that are not z's
        assert not np.any(f32(got_dz)[..., :before])
        assert not np.any(f32(got_dz)[..., before + width:])
    # a head's dz sums 128 products in float32 from bfloat16 operands
    close(got_dz, want_dz, "dz", rtol=2e-4 if per_head else ulp,
          floor=1e-5 if per_head or dtype == jnp.float32 else ulp)
    assert got_dw.shape == (dim,) and got_dw.dtype == jnp.float32
    close(got_dw, want_dw, "dscale", rtol=2e-4, floor=1e-5)
    if hard:                # the row of zeros: eps alone holds it
        assert np.abs(f32(got)[:, 5]).max() == 0
        assert np.all(np.isfinite(f32(got_do)[:, 5]))


def test_what_the_kernels_cannot_tile_is_refused():
    o, scale, gate, _ = operands(1, 1, 32, 4, 16, False, 64, 0, jnp.float32)
    run = lambda o=o, scale=scale, gate=gate, dim=16, act="silu", start=64, \
        **kw: de.delta_epilogue(o, scale, gate, dim, act, start,
                                interpret=True, **kw)
    with pytest.raises(ValueError, match="multiples of 16"):
        run(rows=24)
    with pytest.raises(ValueError, match="the chunk dividing the tile"):
        run(rows=32, chunk=48)
    with pytest.raises(ValueError, match="dividing the 4 heads"):
        run(group=3)
    with pytest.raises(ValueError, match=r"o\(1, 32, 64\).*heads of 24"):
        run(dim=24, scale=jnp.ones(24))
    with pytest.raises(ValueError, match=r"scale\(8,\)"):
        run(scale=jnp.ones(8))
    with pytest.raises(ValueError, match="from column 32"):
        run(start=32)       # no multiple of the 64 columns of z
    with pytest.raises(ValueError, match=r"gate\(1, 32, 128\) from column 1"):
        run(start=128)      # past the array
    with pytest.raises(ValueError, match=r"gate\(1, 16, 128\)"):
        run(gate=gate[:, :16])
    with pytest.raises(ValueError, match="under 'tanh'"):
        run(act="tanh")


@pytest.mark.parametrize("per_head", [True, False],
                         ids=["a_heads_gate", "an_elements_gate"])
def test_the_traced_call_leaves_its_tile_in_the_gauge(monkeypatch, per_head):
    from horovod_tpu.common import basics
    noted = {}
    monkeypatch.setattr(basics, "note_traced", lambda name, what, kinds:
                        noted.update({name: kinds}))
    o, scale, gate, _ = operands(1, 1, 48, 4, 16, per_head, 64, 0,
                                 jnp.float32)
    de.delta_epilogue(o, scale, gate, 16, "silu", 0 if per_head else 64,
                      rows=32, chunk=16)
    assert noted == {"hvd_delta_epilogue": {
        "tile_rows": 32, "tile_columns": 64, "heads": 4,
        "gate_per_head": int(per_head)}}


def test_the_kernels_names_are_no_readers():
    """The benchmark's readers book kernels by prefix
    (``KERNEL_NAMES`` of its families, ``less="kda_"`` of the by-scope
    readers): this time is not the recurrence's nor the prologue's."""
    assert not de.KERNEL_PREFIX.startswith(
        ("kda_", "gdn_", "flash_", "ssm_scan_", "qkv_prologue"))
    assert glm_moe._REMADE_KERNELS == ("qkv_prologue", "delta_epilogue")


# -- the two mixers against their former selves -------------------------------

@pytest.mark.parametrize("name", list(MIXERS))
def test_a_mixer_is_its_former_self_in_bfloat16(name):
    """``tests/former_mixers.py``: against the mixer as it was before
    the epilogue (PR 42), leaf for leaf."""
    p = a_mixer_is_its_former_self_in_bfloat16(name, "epilogue")
    assert p["norm"]["scale"].dtype == jnp.float32
    assert p["norm"]["scale"].shape == (16,)


# The gated norm's leaf in the whole models' trees at the cells' own
# sizes (``tests/test_qkv_prologue.py`` holds the trees' hashes, which
# are the parent's).
NORM_LEAVES = {"ling3flash-injit-1chip": 6, "qwen3next-injit-1chip": 3}


@pytest.mark.parametrize("cell", list(NORM_LEAVES))
def test_the_gated_norms_leaf_is_where_it_was(cell):
    spec = harness.resolve_cell(_paths.manifest(), cell, rehearse=False)
    family = harness.load_module("families", spec["config"]["family"])
    sz = family.sizes(spec["config"],
                      spec["config"]["assumed"]["per_chip_batch"])
    shapes = family.program_shapes(family.build_model(sz), sz)
    flat = weights.flat_shapes(jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), str(a.dtype)), shapes))
    norms = {p: s for p, s in flat.items() if "/mixer/norm/" in p}
    assert len(norms) == NORM_LEAVES[cell]
    assert all(p.endswith("/mixer/norm/scale") and s == ((128,), "float32")
               for p, s in norms.items())


# -- what a recomputed block keeps ------------------------------------------

# the rule's kernels' prefix and the arrays kept beside the arguments
# (``o``, the entering states and the chunks' inverses)
BLOCKS = {"kimi_delta_attention": ("kda", 3), "gated_deltanet": ("gdn", 3)}


@pytest.mark.parametrize("name", list(BLOCKS))
def test_a_recomputed_block_makes_the_epilogues_output_again(name):
    """``_keep_kernel_outputs`` keeps the rule's output ``o``, its
    entering states and its chunks' inverses ``T`` (the backward kernel
    takes no inverse: it has the forward's) and not the epilogue's ``y``
    (134 MB a layer at the cell's size): the backward of a recomputed
    block runs the epilogue's forward kernel again, from the ``o`` it
    kept, and the rule's forward kernel not."""
    rule, kept_arrays = BLOCKS[name]
    calls, kept = a_recomputed_blocks_backward(name)
    assert calls("delta_epilogue_fwd") == 2
    assert calls("delta_epilogue_bwd") == 1
    assert calls("qkv_prologue_fwd") == 2 and calls("qkv_prologue_bwd") == 1
    assert calls(f"{rule}_fwd") == 1 and calls(f"{rule}_bwd") == 1
    assert len(kept) == kept_arrays, kept
    assert not any("epilogue" in k or "prologue" in k for k in kept)
    # the one chunk of 64 that holds the block's 40 positions: ``T`` is
    # [B, H, chunks, C, C] float32 out of the forward kernel's call
    inverses = [k for k in kept if re.match(r"f32\[1,\d+,1,64,64\] ", k)]
    assert len(inverses) == 1 and f"_{rule}_fwd" in inverses[0], kept
