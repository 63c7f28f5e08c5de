"""The flash kernels compile for the chip the benchmark runs on, and
the data-parallel step's all-reduces are scheduled under its backward.

The TPU's compiler is installed wherever the tests run and compiles
for a chip that is described, not attached (``v5e:2x2``, device kind
``TPU v5 lite``). Interpret mode on the CPU cannot see what it refuses:
a slice off the tiling, too much VMEM. Each kernel case is one or two
seconds; the whole-step compiles at the cells' widths (20 to 170 s)
stay out of tier-1, and narrow steps over a mesh of the four described
chips (5 to 15 s each) stand in for them.

Such compiles write persistent-cache entries that cannot be read back
without a chip, so the cache is off around this file. The suite's CPU
compiles skip the optimiser (``tests/conftest.py``); what these tests
assert on is the TPU compiler's own work, so this file keeps it whole.

The TPU's compiler takes a core or two a program and the file's
programs are independent of one another, so every test asks one fixture
(``compiled``) for its executable by the name of its lowering and its
arguments, and the suite's pool of threads (``tests/compiled.py``)
compiles the programs of the tests that follow (``PROGRAMS``, in the
tests' order) meanwhile: no program is built twice, and none waits in a
row behind the others.
"""

import contextlib
import math
import os
from unittest import mock

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs in /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from horovod_tpu.parallel.flash_attention import (  # noqa: E402
    _flash_bhsd, _flash_bwd_bhsd, _ladders_for, _subtile_for,
)

from .compiled import ahead_of  # noqa: E402

pytestmark = [pytest.mark.fast, pytest.mark.usefixtures("whole_compiler"),
              pytest.mark.interpreter_of_its_own]


def test_the_compiler_is_whole_here():
    assert not jax.config.read("jax_disable_most_optimizations")


@pytest.fixture(scope="module")
def topo():
    """The four described chips of a v5e host, the compile cache off
    while they are in use."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it knows no such chip
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip, as a sharding for abstract arguments."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """The mesh of ``lm-injit-4chip``: ``data``=4 over the host's chips."""
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices), ("data",))


def _top(head_dim):
    """The tiles ``flash_attention`` picks for S=2048 at ``head_dim``."""
    q_ladder, k_ladder = _ladders_for(head_dim)
    return q_ladder[0], k_ladder[0]


# (case id, BH, S, D, block_q, block_k)
_CASES = [
    ("bench-d128-512x1024", 64, 2048, 128, 512, 1024),
    ("bench-d128-128x128", 64, 2048, 128, 128, 128),
    ("d64-512x1024", 64, 2048, 64, *_top(64)),
    ("d256-ladder", 32, 2048, 256, *_top(256)),
    # the latent attention of glm47flash-injit-1chip: B4 x 20 heads
    ("glm-d256-s4096", 80, 4096, 256, *_top(256)),
    ("d512-ladder", 16, 2048, 512, *_top(512)),
    ("ring-shard-s512", 64, 512, 128, 512, 512),
]


# The cells' own flash calls: (cell, BH, S, D) at the tile and the
# causal sub-tile `flash_attention` picks for them.
_CELL_SHAPES = [
    ("lm-injit", 64, 2048, 128),            # B4 x 16 heads of 128
    ("glm47flash-injit", 80, 4096, 256),    # B4 x 20 heads of 256
]

# The four sparse cells' routers: (cell, d, experts, k, groups, the
# best, scoring)
_ROUTERS = [("qwen3next-injit", 2048, 512, 10, 1, 1, "softmax"),
            ("ling3flash-injit", 2560, 512, 8, 8, 4, "sigmoid"),
            ("lfm2moe-injit", 2048, 64, 4, 1, 1, "sigmoid"),
            ("glm47flash-injit", 2048, 64, 4, 1, 1, "sigmoid")]
_ROUTER_ROWS = 2048

# (case id, q, k and v heads of 128, columns of x)
_PROLOGUE_SHAPES = [("kimi_delta_attention", 32, 32, 32, 12288),
                    ("gated_deltanet_in_qkvz", 16, 16, 32, 12288)]

# (case id, the gate's shape, its type, its activation, the column z
#  starts at)
_EPILOGUE_SHAPES = [
    ("kimi_delta_attention", (1, 16384, 32), jnp.float32, "sigmoid", 0),
    ("gated_deltanet_in_qkvz", (1, 16384, 12288), jnp.bfloat16, "silu",
     8192)]


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')


# -- the lowerings: ``(chip, mesh4, *arguments)`` -> ``jax.stages.Lowered`` --

def _arr(chip, *shape, dt=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dt, sharding=chip)


def _grads_of_the_sum(f, n_args, value=False):
    """The gradients of ``sum(f(*args))`` in every argument; with
    ``value`` the sum too, where the backward alone would drop the
    forward kernel from the program."""
    def loss(*args):
        out = f(*args)
        if isinstance(out, tuple):
            return sum(jnp.sum(o.astype(jnp.float32)) for o in out)
        return jnp.sum(out.astype(jnp.float32))
    return jax.jit((jax.value_and_grad if value else jax.grad)(
        loss, argnums=tuple(range(n_args))))


def _lower_flash(chip, mesh4, which, bh, seq, d, block_q, block_k, causal):
    """The forward (``"fwd"``) or the backward (``"bwd"``) of the flash
    kernels (the two cells' causal shapes are cases of the ladder's
    tests too)."""
    qkv = _arr(chip, bh, seq, d)
    stat = _arr(chip, bh, 1, seq, dt=jnp.float32)
    offsets = _arr(chip, 2, dt=jnp.int32)
    tiles = dict(causal=causal, block_q=block_q, block_k=block_k,
                 interpret=False)
    if which == "fwd":
        return _flash_bhsd.lower(qkv, qkv, qkv, offsets, **tiles)
    return _flash_bwd_bhsd.lower(
        qkv, qkv, qkv, qkv, stat, stat, offsets, **tiles)


def _phi4flash_blocks(chip):
    from horovod_tpu.parallel.flash_attention import _blocks_for
    return _blocks_for(_arr(chip, 1, 16384, 20, 64),
                       _arr(chip, 1, 16384, 10, 64), None, None)


def _lower_phi4flash(chip, mesh4, which, window):
    seq, d, dv = 16384, 64, 128
    q, k, v = _arr(chip, 20, seq, d), _arr(chip, 10, seq, d), \
        _arr(chip, 10, seq, dv)
    do = _arr(chip, 20, seq, dv, dt=jnp.float32)
    stat = _arr(chip, 20, 1, seq, dt=jnp.float32)
    offsets = _arr(chip, 2, dt=jnp.int32)
    block_q, block_k = _phi4flash_blocks(chip)
    args = dict(causal=True, block_q=block_q, block_k=block_k,
                interpret=False, window=window)
    if which == "fwd":
        return _flash_bhsd.lower(q, k, v, offsets, out_dtype=jnp.float32,
                                 **args)
    return _flash_bwd_bhsd.lower(q, k, v, do, stat, stat, offsets, **args)


def _lower_selective_scan(chip, mesh4):
    from horovod_tpu.parallel import ssm_scan as ss
    bt, seq, channels, states = 1, 16384, 5120, 16
    f32 = dict(dt=jnp.float32)
    return _grads_of_the_sum(
        lambda *x: ss.selective_scan(*x, interpret=False), 6).lower(
            _arr(chip, bt, seq, channels),
            _arr(chip, bt, seq, channels, **f32),
            _arr(chip, channels, states, **f32),
            _arr(chip, bt, seq, states, **f32),
            _arr(chip, bt, seq, states, **f32), _arr(chip, channels, **f32))


def _lower_gated_delta_rule(chip, mesh4):
    from horovod_tpu.parallel import gated_delta as gd
    bt, seq, key_heads, value_heads, d = 1, 16384, 16, 32, 128
    gate = _arr(chip, bt, seq, value_heads, dt=jnp.float32)
    return _grads_of_the_sum(
        lambda *x: gd.gated_delta_rule(*x, interpret=False), 5).lower(
            *2 * [_arr(chip, bt, seq, key_heads, d)],
            _arr(chip, bt, seq, value_heads, d), gate, gate)


def _lower_delta_layer_off_the_lane_tile(chip, mesh4):
    """Olmo-Hybrid's linear layer between its projections at the cell's
    shape (one row of 8,192, 30 key heads of 96 over 30 value heads of
    192): ``qkvz`` laid out 96 columns in 128 lanes, the prologue, the
    rule handed its heads as they come (it lays them out itself) with
    ``beta`` to 2, the epilogue, ``y`` taken back."""
    from horovod_tpu.parallel import delta_epilogue as de
    from horovod_tpu.parallel import gated_delta as gd
    from horovod_tpu.parallel import qkv_prologue as qp
    bt, seq, heads, dk, dv = 1, 8192, 30, 96, 192
    keys, values = heads * dk, heads * dv

    def layer(qkvz, taps, scale, g, beta):
        laid = gd.lay_heads(qkvz, dk)
        q, k, v = (gd.take_heads(t, dk) for t in qp.qkv_prologue(
            laid, gd.lay_heads(taps, dk), gd.laid_columns(dk, dk),
            2 * heads, heads, key_dim=dk, interpret=False))
        o = gd.gated_delta_rule(
            q.reshape(bt, seq, heads, dk), k.reshape(bt, seq, heads, dk),
            v.reshape(bt, seq, heads, dv), g, beta, beta_max=2.0,
            interpret=False)
        y = de.delta_epilogue(
            gd.lay_heads(o.reshape(bt, seq, values), dk),
            gd.lay_heads(scale, dk), laid, gd.laid_columns(dv, dk), "silu",
            gd.laid_columns(2 * keys + values, dk), filled=dv,
            interpret=False)
        return gd.take_heads(y, dk)

    gate = _arr(chip, bt, seq, heads, dt=jnp.float32)
    return _grads_of_the_sum(layer, 5, value=True).lower(
        _arr(chip, bt, seq, 2 * keys + 2 * values),
        _arr(chip, 4, 2 * keys + values, dt=jnp.float32),
        _arr(chip, dv, dt=jnp.float32), gate, gate)


def _lower_kimi_delta_attention(chip, mesh4):
    from horovod_tpu.parallel import kda
    bt, seq, heads, d = 1, 16384, 32, 128
    qkv = _arr(chip, bt, seq, heads, d)
    return _grads_of_the_sum(
        lambda *x: kda.kimi_delta_attention(*x, interpret=False), 5).lower(
            qkv, qkv, qkv, _arr(chip, bt, seq, heads, d, dt=jnp.float32),
            _arr(chip, bt, seq, heads, dt=jnp.float32))


def _lower_prologue(chip, mesh4, hq, hk, hv, columns):
    from horovod_tpu.parallel import qkv_prologue as qp
    seq, d = 16384, 128
    # the backward needs x and the kernel alone: the value keeps the
    # forward kernel in the program
    return _grads_of_the_sum(
        lambda x, w: qp.qkv_prologue(x, w, d, hq + hk, hq, interpret=False),
        2, value=True).lower(
            _arr(chip, 1, seq, columns),
            _arr(chip, 4, (hq + hk + hv) * d, dt=jnp.float32))


def _lower_epilogue(chip, mesh4, gate, gate_type, activation, start):
    from horovod_tpu.parallel import delta_epilogue as de
    # the backward needs o, the scale and the gate alone: the value
    # keeps the forward kernel in the program
    return _grads_of_the_sum(
        lambda o, w, z: de.delta_epilogue(o, w, z, 128, activation, start,
                                          interpret=False),
        3, value=True).lower(
            _arr(chip, 1, 16384, 4096), _arr(chip, 128, dt=jnp.float32),
            _arr(chip, *gate, dt=gate_type))


def _lower_latent_flash(chip, mesh4):
    from horovod_tpu.parallel.flash_attention import flash_attention
    arr = lambda d: _arr(chip, 1, 16384, 32, d)
    return _grads_of_the_sum(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), 3).lower(
            arr(192), arr(192), arr(128))


def _lower_smallthinker_flash(chip, mesh4, window):
    from horovod_tpu.parallel.flash_attention import flash_attention
    arr = lambda heads: _arr(chip, 1, 16384, heads, 128)
    return _grads_of_the_sum(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=window,
                                        interpret=False), 3).lower(
            arr(28), arr(4), arr(4))


def _router_config(cell):
    from horovod_tpu.models import glm_moe, lfm2, ling3flash, qwen3next
    return {"qwen3next-injit": qwen3next.Qwen3NextConfig,
            "ling3flash-injit": ling3flash.Ling3FlashConfig,
            "lfm2moe-injit": lfm2.Lfm2MoeConfig,
            "glm47flash-injit": glm_moe.GlmMoeConfig}[cell]()


def _lower_router(chip, mesh4, d, e, k, n_group, topk_group, scoring):
    """The value and gradients of the expert layer's router alone
    (``glm_moe.route``) over 2,048 bfloat16 rows at a cell's published
    widths (two cells that differ in the weights' scale and constant
    alone share a program)."""
    import dataclasses
    from horovod_tpu.models import glm_moe
    cfg = dataclasses.replace(
        glm_moe.GlmMoeConfig(), hidden_size=d, n_routed_experts=e,
        num_experts_per_tok=k, n_group=n_group, topk_group=topk_group,
        scoring=scoring)
    bias = None if scoring == "softmax" else _arr(chip, e, dt=jnp.float32)

    def loss(x, w, bias, weight):
        chosen, gates = glm_moe.route(x, w, bias, cfg)
        return jnp.sum(gates * weight), chosen
    return jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)).lower(
            _arr(chip, _ROUTER_ROWS, d), _arr(chip, d, e, dt=jnp.float32),
            bias, _arr(chip, _ROUTER_ROWS, k, dt=jnp.float32))


@pytest.mark.parametrize("bh,seq,d,block_q,block_k",
                         [c[1:] for c in _CASES],
                         ids=[c[0] for c in _CASES])
def test_flash_forward_compiles_for_v5e(compiled, bh, seq, d, block_q,
                                        block_k):
    fwd = compiled("flash", "fwd", bh, seq, d, block_q, block_k, True)
    assert _kernel_calls(fwd) == 1


@pytest.mark.parametrize("bh,seq,d,block_q,block_k",
                         [c[1:] for c in _CASES],
                         ids=[c[0] for c in _CASES])
def test_flash_backward_compiles_for_v5e(compiled, bh, seq, d, block_q,
                                         block_k):
    bwd = compiled("flash", "bwd", bh, seq, d, block_q, block_k, True)
    assert _kernel_calls(bwd) == 2  # dq, and dk/dv


def _are_the_kernels_of(executable, *names):
    assert _kernel_calls(executable) == len(names)
    for name in names:          # the benchmark's readers match by name
        assert name in executable.as_text()


def _are_the_flash_kernels(fwd, bwd):
    _are_the_kernels_of(fwd, "flash_fwd")
    _are_the_kernels_of(bwd, "flash_bwd_dq", "flash_bwd_dkv")


@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "noncausal"])
@pytest.mark.parametrize("bh,seq,d", [c[1:] for c in _CELL_SHAPES],
                         ids=[c[0] for c in _CELL_SHAPES])
def test_cells_kernels_compile_with_their_subtiles(compiled, bh, seq, d,
                                                   causal):
    """Forward, dq and dk/dv at the two cells' shapes, the sub-tile
    loops on runtime offsets included: a VMEM or Mosaic refusal of the
    chosen sub-tile shows here, before the chip is asked."""
    block_q, block_k = _top(d)
    sub_q, sub_k = _subtile_for(d, block_q, block_k)
    assert block_q % sub_q == 0 and block_k % sub_k == 0
    assert (sub_q, sub_k) != (block_q, block_k)
    _are_the_flash_kernels(*(
        compiled("flash", which, bh, seq, d, block_q, block_k, causal)
        for which in ("fwd", "bwd")))


# phi4flash-injit-1chip (PR 31): one row of 16,384, two flash calls a
# layer of 20 query heads over 10 key-value heads, key head 64, value
# head 128, inside the window of 512 and over the whole prefix.
@pytest.mark.parametrize("window", [512, None], ids=["window", "full"])
def test_phi4flash_kernels_compile_for_v5e(compiled, chip, window):
    """Grouped heads, a value head of its own size, head size 64, the
    windowed grid and a float32 output (and so a float32 ``do``)
    through the TPU's compiler at the cell's shape and the tiles
    ``flash_attention`` picks there."""
    assert _phi4flash_blocks(chip) == (1024, 1024)
    _are_the_flash_kernels(*(compiled("phi4flash", which, window)
                             for which in ("fwd", "bwd")))


def test_the_selective_scan_compiles_for_v5e(compiled):
    """``ssm_scan_fwd`` and ``ssm_scan_bwd`` at the cell's shape (one
    row of 16,384, 5,120 channels, 16 states) and the ladder's chunk:
    scalars from SMEM, registers of 1,024 channels, the backward's
    chunk of states in VMEM."""
    _are_the_kernels_of(compiled("selective_scan"),
                        "ssm_scan_fwd", "ssm_scan_bwd")


def test_the_gated_delta_rule_compiles_for_v5e(compiled):
    """``gdn_fwd`` and ``gdn_bwd`` at the cell's shape (one row of
    16,384, 16 key heads serving 32 value heads of 128) and the ladder's
    chunk: two value heads a grid step, the triangular inverse's
    float32 products, a head's whole table of gates resident."""
    _are_the_kernels_of(compiled("gated_delta_rule"), "gdn_fwd", "gdn_bwd")


def test_the_delta_rule_compiles_at_heads_of_96_over_192(compiled):
    """``olmohybrid-injit-1chip``'s linear layer between its
    projections: the prologue's, the rule's and the epilogue's two
    kernels each, under the names the benchmark's readers match, at
    heads that are no whole lane tiles (96 columns laid out in 128
    lanes, a value head of 192 in 256) and with the chunk's inverse by
    blocks (``beta`` to 2)."""
    _are_the_kernels_of(
        compiled("delta_layer_off_the_lane_tile"),
        "qkv_prologue_fwd", "qkv_prologue_bwd", "gdn_fwd", "gdn_bwd",
        "delta_epilogue_fwd", "delta_epilogue_bwd")


def test_kimi_delta_attention_compiles_for_v5e(compiled):
    """``kda_fwd`` and ``kda_bwd`` at the cell's shape (one row of
    16,384, 32 heads of 128, a float32 log-decay a key channel) and the
    ladder's chunk and sub-block: the levels' masked products, the
    diagonal level and the inverse at full precision, the running sums
    taken inside the kernels."""
    from horovod_tpu.parallel import kda
    _are_the_kernels_of(compiled("kimi_delta_attention"),
                        "kda_fwd", "kda_bwd")
    assert kda._lengths_for(16384) == (128, 16)


@pytest.mark.parametrize("hq,hk,hv,columns",
                         [c[1:] for c in _PROLOGUE_SHAPES],
                         ids=[c[0] for c in _PROLOGUE_SHAPES])
def test_the_delta_rules_prologue_compiles_for_v5e(compiled, hq, hk, hv,
                                                   columns):
    """``qkv_prologue_fwd`` and ``qkv_prologue_bwd`` at the two cells'
    shapes (one row of 16,384, bfloat16; the Gated DeltaNet's 8,192
    columns are the leading ones of ``qkvz``'s 12,288) and the ladder's
    tile: a head's columns at a dynamic offset, the taps' reads off the
    float32 tiling, the tile's blocks inside the kernels' VMEM."""
    _are_the_kernels_of(compiled("prologue", hq, hk, hv, columns),
                        "qkv_prologue_fwd", "qkv_prologue_bwd")


@pytest.mark.parametrize("gate,gate_type,activation,start",
                         [c[1:] for c in _EPILOGUE_SHAPES],
                         ids=[c[0] for c in _EPILOGUE_SHAPES])
def test_the_delta_rules_epilogue_compiles_for_v5e(compiled, gate, gate_type,
                                                   activation, start):
    """``delta_epilogue_fwd`` and ``delta_epilogue_bwd`` at the two
    cells' shapes (one row of 16,384, 32 heads of 128, bfloat16; Kimi
    delta attention's gate a head's float32 scalar, the Gated
    DeltaNet's the last 4,096 of ``qkvz``'s 12,288 columns) and the
    ladder's tile: a head's columns at a dynamic offset, a head's gate
    by a masked row sum of a tile 32 lanes wide, the tile's blocks
    inside the kernels' VMEM."""
    _are_the_kernels_of(
        compiled("epilogue", gate, gate_type, activation, start),
        "delta_epilogue_fwd", "delta_epilogue_bwd")


def test_the_flash_kernels_compile_at_a_score_head_of_192_over_a_value_head_of_128(
        compiled):
    """The latent attention of ``ling3flash-injit-1chip``: 32 heads, q
    and k of 128 + 64, v and o of 128, S 16,384, the ladder's tiles for
    a head of 192 (512x1024: 1024x1024 does not fit VMEM in the dk/dv
    kernel there)."""
    assert _kernel_calls(compiled("latent_flash")) == 3
    assert _top(192) == (512, 1024)


def test_the_flash_kernels_compile_at_a_window_of_4096_and_seven_heads_a_group(
        compiled):
    """A windowed layer of ``smallthinker-injit-1chip``: 28 query heads
    over 4 key-value heads of 128, S 16,384, a window of 4,096 at a
    windowed call's 1024x1024 tiles (the causal ladder's pair at this
    head is 512x1024): whole tiles between the window's two diagonals
    (``_over_tile``'s unmasked branch under a window, which phi4flash's
    window of 512 never takes) and a group of seven's accumulators in
    the dk/dv kernel's VMEM."""
    assert _kernel_calls(compiled("smallthinker_flash", 4096)) == 3
    assert _top(128) == (512, 1024)
    assert _ladders_for(128, 4096)[0][0] == 1024 == _ladders_for(64)[0][0]


@pytest.mark.parametrize("cell,d,e,k,n_group,topk_group,scoring", _ROUTERS,
                         ids=[c[0] for c in _ROUTERS])
def test_the_router_compiles_without_a_sort_a_gather_or_a_scatter(
        compiled, cell, d, e, k, n_group, topk_group, scoring):
    """``glm_moe.route`` at each sparse cell's router, value and
    gradients: the optimised program holds no ``sort`` (what
    ``jax.lax.top_k`` is on this chip), no ``gather`` and no
    ``scatter``, and its three products (the logits, ``dx``, ``dW``)
    are float32 at full precision."""
    import re
    cfg = _router_config(cell)
    assert (cfg.hidden_size, cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.n_group, cfg.topk_group, cfg.scoring) \
        == (d, e, k, n_group, topk_group, scoring)
    text = compiled("router", d, e, k, n_group, topk_group,
                    scoring).as_text()
    for op in ("sort", "gather", "scatter"):
        assert not re.search(rf"\b{op}\(", text), op
    products = re.findall(r"= f32\[[\d,]+\]\S* convolution\(.*", text)
    assert len(products) == 3
    assert all("operand_precision={highest,highest}" in p for p in products)


def test_d256_keeps_the_default_pair_and_d512_is_halved():
    """The D=256 cases above compile the pair the chip measured fastest
    of the ladder there (PR 27), the D=512 case the halved one."""
    assert _top(128) == _top(256) == (512, 1024)
    assert _top(512) == (256, 512)


# -- the data-parallel step over four chips (spmd/overlap.py) -------------

def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _flash(q, k, v, causal=True):
    # ``best_attention`` picks by the default backend, the CPU's here
    from horovod_tpu.parallel.flash_attention import flash_attention
    return flash_attention(q, k, v, causal=causal, interpret=False)


def _lower_lm_step(chip, mesh4, num_layers, with_the_options):
    """``lm_train_step`` of a ``TransformerLM`` cut to half the cell's
    width (d 1024: its MLP, head and embedding leaves are 16.8 MB each,
    its attention leaves 4.2 MB), for the mesh; with the compiler
    options the step asks for itself, or as if it asked for none."""
    from horovod_tpu import spmd
    from horovod_tpu.models import train_steps
    from horovod_tpu.models.transformer import (
        TransformerConfig, TransformerLM)
    model = TransformerLM(TransformerConfig(
        vocab_size=4096, num_layers=num_layers, num_heads=8, head_dim=128,
        max_seq_len=256, dtype=jnp.bfloat16, attention_fn=_flash))
    tx = train_steps.distributed_sgd()
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 256), jnp.int32)),
        jax.random.key(0))["params"]
    rep = spmd.replicated_sharding(mesh4)
    tokens = jax.ShapeDtypeStruct((4, 256), jnp.int32,
                                  sharding=spmd.batch_sharding(mesh4))
    asks_for_none = mock.patch.object(
        spmd, "overlap_compiler_options", lambda mesh, axis="data": None)
    with contextlib.nullcontext() if with_the_options else asks_for_none:
        return train_steps.lm_train_step(model, tx, mesh4).lower(
            _abstract(params, rep),
            _abstract(jax.eval_shape(tx.init, params), rep), tokens)


def _parameter_bytes(executable):
    """The float32 bytes of the first argument (the parameters) of a
    step's executable."""
    return sum(4 * math.prod(leaf.shape) for leaf in
               jax.tree_util.tree_leaves(executable.args_info[0][0]))


def test_lm_step_reduces_its_gradients_under_the_backward(mesh4, compiled):
    """With the options the step asks for itself, most of the
    gradients' bytes go in asynchronous pairs that have compute ops
    scheduled between start and done, and the flash kernels are still
    in the executable."""
    from horovod_tpu import spmd
    assert spmd.overlap_compiler_options(mesh4)
    step = compiled("lm_step", 2, True)
    n_bytes = _parameter_bytes(step)
    got = spmd.collective_schedule(step)
    hidden = got["async"]["overlapped"]
    assert got["sync"]["bytes"] + got["async"]["bytes"] == n_bytes + 4
    assert hidden["count"] >= 4
    assert hidden["bytes"] > got["sync"]["bytes"]
    assert hidden["bytes"] == got["async"]["bytes"]
    assert _kernel_calls(step) == 2 * 3     # fwd, dq, dk/dv a layer


def test_lm_steps_device_ops_have_their_scopes(compiled):
    """``spmd.device_scopes`` on the same executable: every flash call
    is under ``attn``, what the head loss's path names under
    ``lm_head_loss`` (the products of its one chunk here), the
    asynchronous pairs and the synchronous all-reduces under
    ``exchange``, and of the instructions that do the device's work
    (fusions, kernels, loops) under a twentieth carry no scope."""
    import re
    from horovod_tpu import spmd
    from horovod_tpu.spmd import overlap
    step = compiled("lm_step", 2, True)
    text = step.as_text()
    table = spmd.device_scopes(step)
    assert table == spmd.device_scopes(text)
    comps = overlap._computations(text)
    lines = {m.group(1): (m.group(3), line)
             for comp, body in comps.items()
             if comp != "ENTRY" for line in body
             for m in [overlap._INSTR.match(line)] if m}

    def multiplies(name):       # a fusion around one of the MXU's products
        called = re.search(r"calls=%?([\w.\-]+)", lines[name][1])
        return called and any(" convolution(" in line
                              for line in comps[called.group(1)])
    kernels = [n for n in table if n.startswith("flash_")]
    assert len(kernels) == 2 * 3 and {table[n] for n in kernels} == {"attn"}
    assert sum(n in table.backward for n in kernels) == 2 * 2
    head = [n for n in table if "jvp(lm_head_loss)" in lines[n][1]]
    assert {table[n] for n in head} == {"lm_head_loss"}
    # the logits, `dlogits @ W^T` and `h^T @ dlogits`
    assert len([n for n in head if multiplies(n)]) == 3
    assert not [n for n in head if n in table.backward]     # one pass
    pairs = spmd.collective_schedule(step)["pairs"]
    assert len(pairs) >= 4
    for pair in pairs:
        assert table[pair["name"]] == "exchange"
        assert table[pair["name"].replace("-start", "-done")] == "exchange"
    reduces = [n for n in table if lines[n][0] == "all-reduce"]
    assert reduces and {table[n] for n in reduces} == {"exchange"}
    work = [n for n in table if overlap._is_compute(*lines[n])]
    assert len(work) > 100
    assert sum(table[n] == "" for n in work) < len(work) / 20
    assert {"loss", "optimizer", "mlp", "embed"} <= set(table.values())


def test_lm_step_without_the_options_reduces_synchronously(compiled):
    """The same step without the options: every all-reduce is
    synchronous. If a libtpu changes that default, this fails and the
    options can go. (One layer shows it: the schedule alone is read.)"""
    from horovod_tpu import spmd
    step = compiled("lm_step", 1, False)
    got = spmd.collective_schedule(step)
    assert got["async"]["count"] == 0
    assert got["sync"]["bytes"] == _parameter_bytes(step) + 4


def _resnet_step(mesh4):
    """``resnet_train_step`` of ResNet-18's four stages at one block
    each (the step is the cell's; what is read is that every gradient
    is reduced, which a block a stage shows; the 50-layer model
    compiles in 22 s, ResNet-18 in 9), 32 x 32 images."""
    from horovod_tpu import spmd
    from horovod_tpu.models import train_steps
    from horovod_tpu.models.resnet import BasicBlock, ResNet
    model = ResNet(stage_sizes=[1, 1, 1, 1], block_cls=BasicBlock,
                   num_classes=train_steps.RESNET_CLASSES,
                   dtype=jnp.bfloat16, axis_name=train_steps.AXIS)
    tx = train_steps.distributed_sgd()
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 32, 32, 3), jnp.bfloat16),
                             train=True), jax.random.key(0))
    rep = spmd.replicated_sharding(mesh4)
    rows = spmd.batch_sharding(mesh4)
    args = (_abstract(variables["params"], rep),
            _abstract(variables["batch_stats"], rep),
            _abstract(jax.eval_shape(tx.init, variables["params"]), rep),
            jax.ShapeDtypeStruct((4, 32, 32, 3), jnp.bfloat16,
                                 sharding=rows),
            jax.ShapeDtypeStruct((4,), jnp.int32, sharding=rows))
    return train_steps.resnet_train_step(model, tx, mesh4), args


def _glm_moe_step(mesh4):
    """``glm_moe_train_step`` of a small sparse decoder: latent
    attention (dense here), two expert layers' worth of routing, the
    multi-token module."""
    from horovod_tpu import spmd
    from horovod_tpu.models import glm_moe, train_steps
    model = glm_moe.GlmMoeLM(glm_moe.GlmMoeConfig(
        vocab_size=512, num_layers=2, hidden_size=128, num_heads=2,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=96,
        qk_rope_head_dim=32, v_head_dim=128, intermediate_size=256,
        moe_intermediate_size=128, n_routed_experts=8,
        num_experts_per_tok=2, experts_held=4))
    tx = train_steps.distributed_sgd()
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 64), jnp.int32)),
        jax.random.key(0))["params"]
    rep = spmd.replicated_sharding(mesh4)
    args = (_abstract(params, rep),
            _abstract(jax.eval_shape(tx.init, params), rep),
            jax.ShapeDtypeStruct((4, 64), jnp.int32,
                                 sharding=spmd.batch_sharding(mesh4)))
    return train_steps.glm_moe_train_step(model, tx, mesh4), args


def _lower_other_step(chip, mesh4, build):
    step, args = build(mesh4)
    return step.lower(*args)


@pytest.mark.parametrize("build", [_resnet_step, _glm_moe_step],
                         ids=["resnet", "glm_moe"])
def test_the_other_steps_compile_with_the_options(compiled, build):
    """No cell runs these two steps over four chips; they take the
    same options there, and the compiler accepts them: the gradients
    are reduced, every one, in one kind or the other."""
    from horovod_tpu import spmd
    step = compiled("other_step", build)
    got = spmd.collective_schedule(step)
    assert got["sync"]["bytes"] + got["async"]["bytes"] \
        >= _parameter_bytes(step)


# -- the one way to an executable -------------------------------------------

LOWERINGS = {
    "flash": _lower_flash, "phi4flash": _lower_phi4flash,
    "selective_scan": _lower_selective_scan,
    "gated_delta_rule": _lower_gated_delta_rule,
    "delta_layer_off_the_lane_tile": _lower_delta_layer_off_the_lane_tile,
    "kimi_delta_attention": _lower_kimi_delta_attention,
    "prologue": _lower_prologue, "epilogue": _lower_epilogue,
    "latent_flash": _lower_latent_flash,
    "smallthinker_flash": _lower_smallthinker_flash,
    "router": _lower_router,
    "lm_step": _lower_lm_step,
    "other_step": _lower_other_step,
}

# What the tests above ask for, in their order and each once: the pool
# works ahead through this list.
PROGRAMS = list(dict.fromkeys([
    *(("flash", which, *case[1:], True)
      for which in ("fwd", "bwd") for case in _CASES),
    *(("flash", which, *cell[1:], *_top(cell[3]), causal)
      for causal in (True, False) for cell in _CELL_SHAPES
      for which in ("fwd", "bwd")),
    *(("phi4flash", which, window)
      for window in (512, None) for which in ("fwd", "bwd")),
    ("selective_scan",), ("gated_delta_rule",),
    ("delta_layer_off_the_lane_tile",), ("kimi_delta_attention",),
    *(("prologue", *case[1:]) for case in _PROLOGUE_SHAPES),
    *(("epilogue", *case[1:]) for case in _EPILOGUE_SHAPES),
    ("latent_flash",), ("smallthinker_flash", 4096),
    *(("router", *case[1:]) for case in _ROUTERS),
    ("lm_step", 2, True), ("lm_step", 1, False),
    ("other_step", _resnet_step), ("other_step", _glm_moe_step),
]))
AHEAD = 8       # programs handed to the pool before they are asked for


@pytest.fixture(scope="module")
def compiled(chip, mesh4):
    """``compiled(lowering, *arguments)``: the executable of
    ``LOWERINGS[lowering](chip, mesh4, *arguments)`` for the described
    chip(s), built once whoever asks. Lowering is Python and stays on
    this thread; ``Lowered.compile()`` releases the interpreter, so the
    suite's pool of threads (``tests/compiled.py``) compiles the asked
    program and the next ``AHEAD`` of ``PROGRAMS`` while the tests
    before them assert."""
    programs = ahead_of(
        lambda program: {"compiled": LOWERINGS[program[0]](
            chip, mesh4, *program[1:])}, PROGRAMS, ahead=AHEAD)
    yield lambda *program: programs(program)["compiled"]
    programs.cancel()
