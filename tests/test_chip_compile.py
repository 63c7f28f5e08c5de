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
"""

import functools
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs in /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from horovod_tpu.parallel.flash_attention import (  # noqa: E402
    _flash_bhsd, _flash_bwd_bhsd, _ladders_for, _subtile_for,
)

pytestmark = [pytest.mark.fast, pytest.mark.usefixtures("whole_compiler")]


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


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def flash_compiled(chip):
    """``flash_compiled(which, BH, S, D, block_q, block_k, causal)``:
    the forward (``"fwd"``) or the backward (``"bwd"``) compiled for
    the chip, once for every case that names the same program (the two
    cells' causal shapes are cases of the ladder's tests too)."""
    @functools.cache
    def compiled(which, bh, seq, d, block_q, block_k, causal):
        qkv = jax.ShapeDtypeStruct((bh, seq, d), jnp.bfloat16,
                                   sharding=chip)
        stat = jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32,
                                    sharding=chip)
        offsets = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=chip)
        tiles = dict(causal=causal, block_q=block_q, block_k=block_k,
                     interpret=False)
        if which == "fwd":
            return _flash_bhsd.lower(
                qkv, qkv, qkv, offsets, **tiles).compile()
        return _flash_bwd_bhsd.lower(
            qkv, qkv, qkv, qkv, stat, stat, offsets, **tiles).compile()
    return compiled


@pytest.mark.parametrize("bh,seq,d,block_q,block_k",
                         [c[1:] for c in _CASES],
                         ids=[c[0] for c in _CASES])
def test_flash_forward_compiles_for_v5e(flash_compiled, bh, seq, d,
                                        block_q, block_k):
    compiled = flash_compiled("fwd", bh, seq, d, block_q, block_k, True)
    assert _kernel_calls(compiled) == 1


@pytest.mark.parametrize("bh,seq,d,block_q,block_k",
                         [c[1:] for c in _CASES],
                         ids=[c[0] for c in _CASES])
def test_flash_backward_compiles_for_v5e(flash_compiled, bh, seq, d,
                                         block_q, block_k):
    compiled = flash_compiled("bwd", bh, seq, d, block_q, block_k, True)
    assert _kernel_calls(compiled) == 2  # dq, and dk/dv


# The cells' own flash calls: (cell, BH, S, D) at the tile and the
# causal sub-tile `flash_attention` picks for them.
_CELL_SHAPES = [
    ("lm-injit", 64, 2048, 128),            # B4 x 16 heads of 128
    ("glm47flash-injit", 80, 4096, 256),    # B4 x 20 heads of 256
]


@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "noncausal"])
@pytest.mark.parametrize("bh,seq,d", [c[1:] for c in _CELL_SHAPES],
                         ids=[c[0] for c in _CELL_SHAPES])
def test_cells_kernels_compile_with_their_subtiles(flash_compiled, bh,
                                                   seq, d, causal):
    """Forward, dq and dk/dv at the two cells' shapes, the sub-tile
    loops on runtime offsets included: a VMEM or Mosaic refusal of the
    chosen sub-tile shows here, before the chip is asked."""
    block_q, block_k = _top(d)
    sub_q, sub_k = _subtile_for(d, block_q, block_k)
    assert block_q % sub_q == 0 and block_k % sub_k == 0
    assert (sub_q, sub_k) != (block_q, block_k)
    fwd, bwd = (flash_compiled(which, bh, seq, d, block_q, block_k, causal)
                for which in ("fwd", "bwd"))
    assert (_kernel_calls(fwd), _kernel_calls(bwd)) == (1, 2)
    for text, names in ((fwd.as_text(), ["flash_fwd"]),
                        (bwd.as_text(), ["flash_bwd_dq", "flash_bwd_dkv"])):
        for name in names:      # the benchmark's readers match by name
            assert name in text


# phi4flash-injit-1chip (PR 31): one row of 16,384, two flash calls a
# layer of 20 query heads over 10 key-value heads, key head 64, value
# head 128, inside the window of 512 and over the whole prefix.
@pytest.mark.parametrize("window", [512, None], ids=["window", "full"])
def test_phi4flash_kernels_compile_for_v5e(chip, window):
    """Grouped heads, a value head of its own size, head size 64, the
    windowed grid and a float32 output (and so a float32 ``do``)
    through the TPU's compiler at the cell's shape and the tiles
    ``flash_attention`` picks there."""
    from horovod_tpu.parallel.flash_attention import _blocks_for
    seq, d, dv = 16384, 64, 128
    arr = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=chip)
    q, k, v, do = arr(20, seq, d), arr(10, seq, d), arr(10, seq, dv), \
        arr(20, seq, dv, dt=jnp.float32)
    stat = arr(20, 1, seq, dt=jnp.float32)
    offsets = arr(2, dt=jnp.int32)
    block_q, block_k = _blocks_for(
        arr(1, seq, 20, d), arr(1, seq, 10, d), None, None)
    assert (block_q, block_k) == (1024, 1024)
    args = dict(causal=True, block_q=block_q, block_k=block_k,
                interpret=False, window=window)
    fwd = _flash_bhsd.lower(q, k, v, offsets, out_dtype=jnp.float32,
                            **args).compile()
    bwd = _flash_bwd_bhsd.lower(q, k, v, do, stat, stat, offsets,
                                **args).compile()
    assert (_kernel_calls(fwd), _kernel_calls(bwd)) == (1, 2)
    for text, names in ((fwd.as_text(), ["flash_fwd"]),
                        (bwd.as_text(), ["flash_bwd_dq", "flash_bwd_dkv"])):
        for name in names:
            assert name in text


def test_the_selective_scan_compiles_for_v5e(chip):
    """``ssm_scan_fwd`` and ``ssm_scan_bwd`` at the cell's shape (one
    row of 16,384, 5,120 channels, 16 states) and the ladder's chunk:
    scalars from SMEM, registers of 1,024 channels, the backward's
    chunk of states in VMEM."""
    from horovod_tpu.parallel import ssm_scan as ss
    bt, seq, channels, states = 1, 16384, 5120, 16
    arr = lambda *shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=chip)
    args = (arr(bt, seq, channels, dt=jnp.bfloat16), arr(bt, seq, channels),
            arr(channels, states), arr(bt, seq, states),
            arr(bt, seq, states), arr(channels))

    def loss(*x):
        return jnp.sum(ss.selective_scan(*x, interpret=False)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))) \
        .lower(*args).compile()
    assert _kernel_calls(compiled) == 2
    for name in ("ssm_scan_fwd", "ssm_scan_bwd"):
        assert name in compiled.as_text()


def test_the_gated_delta_rule_compiles_for_v5e(chip):
    """``gdn_fwd`` and ``gdn_bwd`` at the cell's shape (one row of
    16,384, 16 key heads serving 32 value heads of 128) and the ladder's
    chunk: two value heads a grid step, the triangular inverse's
    float32 products, a head's whole table of gates resident."""
    from horovod_tpu.parallel import gated_delta as gd
    bt, seq, key_heads, value_heads, d = 1, 16384, 16, 32, 128
    arr = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=chip)
    gate = arr(bt, seq, value_heads, dt=jnp.float32)
    args = (arr(bt, seq, key_heads, d), arr(bt, seq, key_heads, d),
            arr(bt, seq, value_heads, d), gate, gate)

    def loss(*x):
        return jnp.sum(gd.gated_delta_rule(*x, interpret=False)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(5)))) \
        .lower(*args).compile()
    assert _kernel_calls(compiled) == 2
    for name in ("gdn_fwd", "gdn_bwd"):
        assert name in compiled.as_text()


def test_kimi_delta_attention_compiles_for_v5e(chip):
    """``kda_fwd`` and ``kda_bwd`` at the cell's shape (one row of
    16,384, 32 heads of 128, a float32 log-decay a key channel) and the
    ladder's chunk and sub-block: the levels' masked products, the
    diagonal level and the inverse at full precision, the running sums
    taken inside the kernels."""
    from horovod_tpu.parallel import kda
    bt, seq, heads, d = 1, 16384, 32, 128
    arr = lambda *shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=chip)
    qkv = arr(bt, seq, heads, d)
    args = (qkv, qkv, qkv, arr(bt, seq, heads, d, dt=jnp.float32),
            arr(bt, seq, heads, dt=jnp.float32))

    def loss(*x):
        return jnp.sum(kda.kimi_delta_attention(*x, interpret=False)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(5)))) \
        .lower(*args).compile()
    assert _kernel_calls(compiled) == 2
    for name in ("kda_fwd", "kda_bwd"):
        assert name in compiled.as_text()
    assert kda._lengths_for(seq) == (128, 16)


# (case id, q, k and v heads of 128, columns of x)
_PROLOGUE_SHAPES = [("kimi_delta_attention", 32, 32, 32, 12288),
                    ("gated_deltanet_in_qkvz", 16, 16, 32, 12288)]


@pytest.mark.parametrize("hq,hk,hv,columns",
                         [c[1:] for c in _PROLOGUE_SHAPES],
                         ids=[c[0] for c in _PROLOGUE_SHAPES])
def test_the_delta_rules_prologue_compiles_for_v5e(chip, hq, hk, hv,
                                                   columns):
    """``qkv_prologue_fwd`` and ``qkv_prologue_bwd`` at the two cells'
    shapes (one row of 16,384, bfloat16; the Gated DeltaNet's 8,192
    columns are the leading ones of ``qkvz``'s 12,288) and the ladder's
    tile: a head's columns at a dynamic offset, the taps' reads off the
    float32 tiling, the tile's blocks inside the kernels' VMEM."""
    from horovod_tpu.parallel import qkv_prologue as qp
    seq, d = 16384, 128
    x = jax.ShapeDtypeStruct((1, seq, columns), jnp.bfloat16, sharding=chip)
    w = jax.ShapeDtypeStruct((4, (hq + hk + hv) * d), jnp.float32,
                             sharding=chip)

    def loss(x, w):
        return sum(jnp.sum(o.astype(jnp.float32)) for o in qp.qkv_prologue(
            x, w, d, hq + hk, hq, interpret=False))

    # the backward needs x and the kernel alone: the value keeps the
    # forward kernel in the program
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))) \
        .lower(x, w).compile()
    assert _kernel_calls(compiled) == 2
    for name in ("qkv_prologue_fwd", "qkv_prologue_bwd"):
        assert name in compiled.as_text()


# (case id, the gate's shape, its activation, the column z starts at)
_EPILOGUE_SHAPES = [
    ("kimi_delta_attention", (1, 16384, 32), jnp.float32, "sigmoid", 0),
    ("gated_deltanet_in_qkvz", (1, 16384, 12288), jnp.bfloat16, "silu",
     8192)]


@pytest.mark.parametrize("gate,gate_type,activation,start",
                         [c[1:] for c in _EPILOGUE_SHAPES],
                         ids=[c[0] for c in _EPILOGUE_SHAPES])
def test_the_delta_rules_epilogue_compiles_for_v5e(chip, gate, gate_type,
                                                   activation, start):
    """``delta_epilogue_fwd`` and ``delta_epilogue_bwd`` at the two
    cells' shapes (one row of 16,384, 32 heads of 128, bfloat16; Kimi
    delta attention's gate a head's float32 scalar, the Gated
    DeltaNet's the last 4,096 of ``qkvz``'s 12,288 columns) and the
    ladder's tile: a head's columns at a dynamic offset, a head's gate
    by a masked row sum of a tile 32 lanes wide, the tile's blocks
    inside the kernels' VMEM."""
    from horovod_tpu.parallel import delta_epilogue as de
    o = jax.ShapeDtypeStruct((1, 16384, 4096), jnp.bfloat16, sharding=chip)
    w = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=chip)
    z = jax.ShapeDtypeStruct(gate, gate_type, sharding=chip)

    def loss(o, w, z):
        return jnp.sum(de.delta_epilogue(
            o, w, z, 128, activation, start, interpret=False)
            .astype(jnp.float32))

    # the backward needs o, the scale and the gate alone: the value
    # keeps the forward kernel in the program
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))) \
        .lower(o, w, z).compile()
    assert _kernel_calls(compiled) == 2
    for name in ("delta_epilogue_fwd", "delta_epilogue_bwd"):
        assert name in compiled.as_text()


def test_the_flash_kernels_compile_at_a_score_head_of_192_over_a_value_head_of_128(
        chip):
    """The latent attention of ``ling3flash-injit-1chip``: 32 heads, q
    and k of 128 + 64, v and o of 128, S 16,384, the ladder's tiles for
    a head of 192 (512x1024: 1024x1024 does not fit VMEM in the dk/dv
    kernel there)."""
    from horovod_tpu.parallel.flash_attention import flash_attention
    arr = lambda d: jax.ShapeDtypeStruct((1, 16384, 32, d), jnp.bfloat16,
                                         sharding=chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=False)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arr(192), arr(192), arr(128)).compile()
    assert _kernel_calls(compiled) == 3
    assert _top(192) == (512, 1024)


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


def _lm_step_compiled(mesh4, num_layers=2):
    """``lm_train_step`` of a two-layer ``TransformerLM`` cut to half
    the cell's width (d 1024: its MLP, head and embedding leaves are
    16.8 MB each, its attention leaves 4.2 MB), compiled for the mesh."""
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
    step = train_steps.lm_train_step(model, tx, mesh4)
    compiled = step.lower(_abstract(params, rep),
                          _abstract(jax.eval_shape(tx.init, params), rep),
                          tokens).compile()
    n_bytes = sum(4 * p.size for p in jax.tree_util.tree_leaves(params))
    return compiled, n_bytes


@pytest.fixture(scope="module")
def lm_step4(mesh4):
    """The step with the options it asks for itself, compiled once for
    every test that reads its executable (no program twice in a run)."""
    return _lm_step_compiled(mesh4)


def test_lm_step_reduces_its_gradients_under_the_backward(mesh4, lm_step4):
    """With the options the step asks for itself, most of the
    gradients' bytes go in asynchronous pairs that have compute ops
    scheduled between start and done, and the flash kernels are still
    in the executable."""
    from horovod_tpu import spmd
    assert spmd.overlap_compiler_options(mesh4)
    compiled, n_bytes = lm_step4
    got = spmd.collective_schedule(compiled)
    hidden = got["async"]["overlapped"]
    assert got["sync"]["bytes"] + got["async"]["bytes"] == n_bytes + 4
    assert hidden["count"] >= 4
    assert hidden["bytes"] > got["sync"]["bytes"]
    assert hidden["bytes"] == got["async"]["bytes"]
    assert _kernel_calls(compiled) == 2 * 3     # fwd, dq, dk/dv a layer


def test_lm_steps_device_ops_have_their_scopes(lm_step4):
    """``spmd.device_scopes`` on the same executable: every flash call
    is under ``attn``, what the head loss's path names under
    ``lm_head_loss`` (the products of its one chunk here), the
    asynchronous pairs and the synchronous all-reduces under
    ``exchange``, and of the instructions that do the device's work
    (fusions, kernels, loops) under a twentieth carry no scope."""
    import re
    from horovod_tpu import spmd
    from horovod_tpu.spmd import overlap
    compiled, _ = lm_step4
    text = compiled.as_text()
    table = spmd.device_scopes(compiled)
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
    pairs = spmd.collective_schedule(compiled)["pairs"]
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


def test_lm_step_without_the_options_reduces_synchronously(
        mesh4, monkeypatch):
    """The same step without the options: every all-reduce is
    synchronous. If a libtpu changes that default, this fails and the
    options can go. (One layer shows it: the schedule alone is read.)"""
    from horovod_tpu import spmd
    monkeypatch.setattr(spmd, "overlap_compiler_options",
                        lambda mesh, axis="data": None)
    compiled, n_bytes = _lm_step_compiled(mesh4, num_layers=1)
    got = spmd.collective_schedule(compiled)
    assert got["async"]["count"] == 0
    assert got["sync"]["bytes"] == n_bytes + 4


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


@pytest.mark.parametrize("build", [_resnet_step, _glm_moe_step],
                         ids=["resnet", "glm_moe"])
def test_the_other_steps_compile_with_the_options(mesh4, build):
    """No cell runs these two steps over four chips; they take the
    same options there, and the compiler accepts them: the gradients
    are reduced, every one, in one kind or the other."""
    from horovod_tpu import spmd
    step, args = build(mesh4)
    compiled = step.lower(*args).compile()
    got = spmd.collective_schedule(compiled)
    n_bytes = sum(4 * p.size for p in jax.tree_util.tree_leaves(args[0]))
    assert got["sync"]["bytes"] + got["async"]["bytes"] >= n_bytes
