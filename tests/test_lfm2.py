"""LFM2-24B-A2B's layers (``horovod_tpu/models/lfm2.py``) at a small
size on the CPU with seeded weights: the gated short convolution against
a literal loop over positions, the attention layer's q and k norms by
hand, the whole model's loss and gradients against a plain float32
reference written here and against the one the chip benchmark keeps
(``benchmarks/chip/families/lfm2_moe_lm.py``), the parameter count by
ISSUE 39's formulas at two sizes, and the kept layers under their
published indices. (Cold on this sandbox: 35 s.)"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .chip_bench import _paths  # noqa: F401  (makes chipbench importable)
from .compiled import (beside, out_and_vjp, step_on_a_mesh_of_one,
                       weights_under)
from chipbench import check, harness, weights

import horovod_tpu.jax as hvd
from horovod_tpu.models import lfm2, train_steps
from horovod_tpu.parallel import flash_attention as fa

pytestmark = [pytest.mark.fast, pytest.mark.time_limit(120),
              pytest.mark.interpreter_of_its_own]

FAMILY = harness.load_module("families", "lfm2_moe_lm")
D, HEADS, KV, HD, MLP, WIDTH, EXPERTS, HELD, OFFSET, K, VOCAB, SEQ = \
    32, 4, 2, 8, 48, 16, 16, 4, 8, 4, 64, 24
KEPT = (0, 2, 3)         # conv + dense, attention + experts, conv + experts
EPS, NORMALISER_EPS, THETA = 1e-5, 1e-6, 1e6
CONFIG = {
    "vocab_size": VOCAB, "num_hidden_layers": len(KEPT), "hidden_size": D,
    "layer_types": list(lfm2.PUBLISHED_LAYER_TYPES), "num_dense_layers": 2,
    "intermediate_size": MLP, "num_attention_heads": HEADS,
    "num_key_value_heads": KV, "rope_parameters": {"rope_theta": THETA},
    "conv_L_cache": 3, "moe_intermediate_size": WIDTH,
    "num_experts": HELD, "num_experts_per_tok": K,
    "routed_scaling_factor": 1, "norm_eps": EPS, "kept_layers": list(KEPT),
    "deployment": {"router_width": EXPERTS, "expert_offset": OFFSET},
    "assumed": {"sequence_length": SEQ, "head_dim": HD,
                "topk_weight_eps": NORMALISER_EPS}}
SZ = FAMILY.sizes(CONFIG, 2)
TOL = dict(rtol=3e-5, atol=3e-6)


def in_float32(model):
    return lfm2.Lfm2MoeLM(dataclasses.replace(model.cfg, dtype=jnp.float32))


@pytest.fixture(scope="module")
def model():
    return in_float32(FAMILY.build_model(SZ))


@pytest.fixture(scope="module")
def params():
    shapes, fans = FAMILY.param_shapes(SZ)
    p = weights.make_tree(shapes, fans, seed=21, stream=0)["params"]
    # norm scales start at one: seeded ones, so that a scale left out or
    # one shared where two are meant shows
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: 1.0 + 0.3 * jax.random.normal(
            jax.random.key(len(jax.tree_util.keystr(path))), leaf.shape)
        if path[-1].key == "scale" else leaf, p)


def tokens():
    return FAMILY.make_batch(SZ, 2)(jax.random.key(5))[0]


@pytest.fixture(scope="module")
def programs(model, params):
    """The file's whole-model programs, lowered at its start and
    compiled beside one another and beside the tests before the first
    that asks (``tests/compiled.py``): the program's loss and
    gradients, the plain reference's, and the step on the counted path
    with the state it is to train (``step_state``). The step is a
    program of its own, not the first plus an update: ``shard_map``
    over the mesh, the distributed optimizer's exchange, the state
    donated."""
    t = tokens()
    step, state = step_on_a_mesh_of_one(
        train_steps.lfm2_train_step, model, params, t)
    return beside(
        loss_and_grads=jax.jit(jax.value_and_grad(
            train_steps.lfm2_loss_fn(model), has_aux=True)).lower(
                params, t),
        plain=jax.jit(jax.value_and_grad(plain_loss)).lower(params, t),
        step=step, step_state=state)


def flat(tree):
    return {k: v[0] for k, v in weights.flat_shapes(
        jax.tree_util.tree_map(lambda a: (np.asarray(a),), tree)).items()}


# -- the plain reference, written here ---------------------------------------

def rms(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def rope(x):
    """[B, S, H, hd]: the whole head, halves paired."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[1])[:, None] \
        * THETA ** (-jnp.arange(half) / half)
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def plain_short_conv(p, h):
    bcu = h @ p["in_proj"]["kernel"]
    b, c, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
    z = jnp.pad(b * u, ((0, 0), (2, 0), (0, 0)))
    conv = sum(z[:, j:j + h.shape[1]] * p["conv"]["kernel"][j]
               for j in range(3))
    return (c * conv) @ p["out_proj"]["kernel"]


def plain_attention(p, h):
    q = rms(jnp.einsum("bsd,dhe->bshe", h, p["q"]["kernel"]),
            p["q_norm"]["scale"])
    k = rms(jnp.einsum("bsd,dhe->bshe", h, p["k"]["kernel"]),
            p["k_norm"]["scale"])
    v = jnp.einsum("bsd,dhe->bshe", h, p["v"]["kernel"])
    k, v = (jnp.repeat(t, HEADS // KV, 2) for t in (rope(k), v))
    scores = jnp.einsum("bqhe,bkhe->bhqk", rope(q), k) / math.sqrt(HD)
    seen = jnp.tril(jnp.ones((h.shape[1], h.shape[1]), bool))
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhe,hed->bqd", probs, v, p["o"]["kernel"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def plain_experts(p, h, held=HELD, offset=OFFSET):
    """Sigmoids; the four largest of score + bias; the chosen scores
    over (their sum + 1e-6); the held experts' dense masked sum; no
    shared expert."""
    s = jax.nn.sigmoid(h @ p["router"]["kernel"])
    _, chosen = jax.lax.top_k(s + p["router"]["bias"], K)
    picked = s * jnp.sum(jax.nn.one_hot(chosen, EXPERTS), axis=-2)
    w = picked / (jnp.sum(picked, -1, keepdims=True) + NORMALISER_EPS)
    e = p["experts"]
    return sum(w[..., offset + j, None] * swiglu(
        h, e["gate"][j], e["up"][j], e["down"][j]) for j in range(held))


def plain_loss(p, t):
    table = p["embed"]["embedding"]
    x = table[t]
    for i in KEPT:
        lp = p[f"layer_{i}"]
        h = rms(x, lp["operator_norm"]["scale"])
        x = x + (plain_attention if i % 4 == 2 else plain_short_conv)(
            lp["operator"], h)
        h = rms(x, lp["ffn_norm"]["scale"])
        x = x + (swiglu(h, *(lp["mlp"][n]["kernel"]
                             for n in ("gate", "up", "down")))
                 if i < 2 else plain_experts(lp["moe"], h))
    logp = jax.nn.log_softmax(
        rms(x, p["norm_f"]["scale"])[:, :-1] @ table.T, -1)
    return -jnp.mean(jnp.take_along_axis(logp, t[:, 1:, None], -1))


# -- the tests ----------------------------------------------------------------

def test_the_family_names_the_programs_own_parameters(model):
    program = harness.Program.__new__(harness.Program)
    program.family, program.sz, program.model = FAMILY, SZ, model
    program.shapes, _ = FAMILY.param_shapes(SZ)
    program._check_shapes()


def test_the_kept_layers_carry_their_published_indices():
    """Attention at 2, 6, ..., 38 of 40 and the convolution elsewhere;
    the cell's nine are layer 0 and two whole periods, and each sits
    under its own index in the parameter tree."""
    kinds = lfm2.PUBLISHED_LAYER_TYPES
    assert len(kinds) == 40 and kinds.count("full_attention") == 10
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] \
        == list(range(2, 40, 4))
    cell = FAMILY.sizes(cell_config(), 4)
    assert cell["kept"] == (0, 2, 3, 4, 5, 6, 7, 8, 9)
    assert FAMILY.kinds(cell) == [("conv", "dense")] + 2 * (
        [("attention", "experts")] + 3 * [("conv", "experts")])
    tree = FAMILY.program_shapes(FAMILY.build_model(cell), cell)["params"]
    assert sorted(k for k in tree if k.startswith("layer_")) \
        == [f"layer_{i}" for i in cell["kept"]]
    for i in cell["kept"]:
        assert ("q_norm" in tree[f"layer_{i}"]["operator"]) == (i in (2, 6))
        assert ("mlp" in tree[f"layer_{i}"]) == (i == 0)
        assert ("moe" in tree[f"layer_{i}"]) == (i != 0)
    assert lfm2.Lfm2MoeConfig().layers == tuple(range(40))


def cell_config():
    with open(os.path.join(_paths.BENCH, "configs",
                           "lfm2-24b-a2b-ep8-l9.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("size", ["tiny", "the_cell"])
def test_the_parameter_count_is_the_formulas(size):
    """ISSUE 39's count by hand: layer 0, a sparse convolution layer, a
    sparse attention layer, the table, the final norm."""
    sz = SZ if size == "tiny" else FAMILY.sizes(cell_config(), 4)
    d, hd = sz["d"], sz["head_dim"]
    conv = 4 * d * d + sz["taps"] * d
    attention = d * sz["heads"] * hd + 2 * d * sz["kv_heads"] * hd \
        + sz["heads"] * hd * d + 2 * hd
    experts = d * sz["experts"] + sz["experts"] \
        + sz["experts_held"] * 3 * d * sz["expert_mlp"]
    want = sz["vocab"] * d + d
    for op, ff in FAMILY.kinds(sz):
        want += (conv if op == "conv" else attention) + 2 * d \
            + (3 * d * sz["mlp"] if ff == "dense" else experts)
    tree = FAMILY.program_shapes(FAMILY.build_model(sz), sz)["params"]
    got = sum(math.prod(leaf.shape)
              for leaf in jax.tree_util.tree_leaves(tree))
    assert got == want == FAMILY.param_count(sz)
    if size == "the_cell":
        assert want == 832_652_032
        assert conv + 2 * d + 3 * d * sz["mlp"] == 89_139_200
        assert conv + 2 * d + experts == 92_416_064
        assert attention + 2 * d + experts == 86_118_592


def test_the_short_convolution_is_the_literal_loop(model, params):
    """Position by position: ``c_t = sum_j w_j (B u)_{t-2+j}``,
    positions before the row's start zero, both gates linear; values
    and every gradient."""
    p = params["layer_3"]["operator"]
    x = jax.random.normal(jax.random.key(3), (2, 10, D))
    cot = jax.random.normal(jax.random.key(4), (2, 10, D))

    def loop(p, x):
        bcu = x @ p["in_proj"]["kernel"]
        b, c, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
        z, w = b * u, p["conv"]["kernel"]
        rows = []
        for t in range(x.shape[1]):
            acc = jnp.zeros_like(z[:, 0])
            for j in range(3):
                if t - 2 + j >= 0:
                    acc = acc + w[j] * z[:, t - 2 + j]
            rows.append(c[:, t] * acc)
        return jnp.stack(rows, 1) @ p["out_proj"]["kernel"]

    got, got_grads = out_and_vjp(
        lambda p, x: lfm2.ShortConv(model.cfg).apply({"params": p}, x),
        cot, p, x)
    want, want_grads = out_and_vjp(loop, cot, p, x)
    np.testing.assert_allclose(got, want, **TOL)
    for g, w in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max() + 1))
    assert set(p) == {"in_proj", "conv", "out_proj"}
    assert set(p["conv"]) == {"kernel"} and p["conv"]["kernel"].shape == (3, D)
    # no activation function: the operator is homogeneous of degree 3
    twice = lfm2.ShortConv(model.cfg).apply({"params": p}, 2 * x)
    np.testing.assert_allclose(twice, 8 * got, rtol=1e-5, atol=1e-5)


def test_q_and_k_are_normalised_a_head_at_a_time(model, params,
                                                 monkeypatch):
    """What reaches the attention call: ``W_q h`` over each head's own
    mean square plus eps, times ONE weight vector for all query heads
    (and one for all key heads), then the rotary, which turns position
    0 by nothing and keeps every head's length."""
    p = params["layer_2"]["operator"]
    assert p["q_norm"]["scale"].shape == (HD,) == p["k_norm"]["scale"].shape
    x = jax.random.normal(jax.random.key(6), (2, SEQ, D))
    seen = {}

    def capture(q, k, v):
        seen.update(q=q, k=k, v=v)
        return fa._dense_reference(q, k, v, True, 0, 0)

    monkeypatch.setattr(lfm2, "best_grouped_attention", capture)
    pos = jnp.broadcast_to(jnp.arange(SEQ)[None], (2, SEQ))
    got = lfm2.NormedGroupedAttention(model.cfg).apply(
        {"params": p}, x, pos)
    for name, heads in (("q", HEADS), ("k", KV)):
        raw = np.einsum("bsd,dhe->bshe", x, p[name]["kernel"])
        assert raw.shape == (2, SEQ, heads, HD)
        by_hand = raw / np.sqrt((raw ** 2).mean(-1, keepdims=True) + EPS) \
            * np.asarray(p[f"{name}_norm"]["scale"])
        np.testing.assert_allclose(seen[name][:, 0], by_hand[:, 0], **TOL)
        np.testing.assert_allclose(
            np.linalg.norm(seen[name], axis=-1),
            np.linalg.norm(by_hand, axis=-1), rtol=1e-5)
        assert float(np.abs(seen[name][:, 1:] - by_hand[:, 1:]).max()) > 1e-3
    assert seen["v"].shape == (2, SEQ, KV, HD)     # v: no norm, no rotary
    np.testing.assert_allclose(
        seen["v"], np.einsum("bsd,dhe->bshe", x, p["v"]["kernel"]), **TOL)
    np.testing.assert_allclose(got, jax.jit(plain_attention)(p, x), **TOL)


def test_attention_runs_through_the_flash_kernels_at_the_cells_heads(
        monkeypatch):
    """32 query heads over 8 key-value heads of 64, the kernels in
    interpret mode against the reference's dense softmax a block of
    queries at a time."""
    config = dict(CONFIG, hidden_size=2048, num_attention_heads=32,
                  num_key_value_heads=8,
                  assumed=dict(CONFIG["assumed"], head_dim=64,
                               sequence_length=32))
    sz = FAMILY.sizes(config, 1)
    shapes, fans = FAMILY.param_shapes(sz)
    p = weights_under(shapes, fans, 7, 0, "params/layer_2/operator")
    calls = []

    def through_kernels(q, k, v):
        calls.append((q.shape, k.shape, v.shape))
        return fa.flash_attention(q, k, v, causal=True, block_q=16,
                                  block_k=16, interpret=True)

    monkeypatch.setattr(lfm2, "best_grouped_attention", through_kernels)
    cfg = dataclasses.replace(FAMILY.build_model(sz).cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(8), (1, 32, 2048))
    pos = jnp.arange(32)[None]
    got = jax.jit(lfm2.NormedGroupedAttention(cfg).apply)(
        {"params": p}, x, pos)
    assert calls == [((1, 32, 32, 64), (1, 32, 8, 64), (1, 32, 8, 64))]
    np.testing.assert_allclose(
        got, jax.jit(FAMILY.reference_fns(sz)["attention"])(p, x),
        rtol=1e-4, atol=1e-5)


def test_the_whole_loss_and_its_gradients_are_the_plain_references(
        programs, params):
    """Against the reference written in this file, and against the chip
    benchmark's, stage by stage as ``check.py`` calls it: loss, counts
    and every leaf's gradient. The expert bias gets none."""
    t = tokens()
    (loss, counts), grads = programs["loss_and_grads"](params, t)
    want_loss, want = programs["plain"](params, t)
    with jax.default_matmul_precision("highest"):
        theirs_loss, _, theirs = check.StagedGradient(
            FAMILY.reference_stages(SZ))(params, {}, (t,))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    np.testing.assert_allclose(theirs_loss, want_loss, rtol=1e-6)
    assert counts.shape == (len(KEPT), HELD + 2)
    assert np.asarray(counts).sum(axis=1).tolist() \
        == [0, t.size * K, t.size * K]           # the dense layer's: zeros
    got, want, theirs = flat(grads), flat(want), flat(theirs)
    assert set(got) == set(want) == set(theirs)
    for path in want:
        for mine in (got[path], theirs[path]):
            np.testing.assert_allclose(
                mine, want[path], rtol=3e-4,
                atol=3e-6 * float(np.abs(want[path]).max() + 1),
                err_msg=path)
    for i in KEPT[1:]:
        assert not got[f"layer_{i}/moe/router/bias"].any()
        assert got[f"layer_{i}/moe/router/kernel"].any()


def test_the_step_trains_on_the_counted_path(programs):
    """``lfm2_train_step``: ``_counted_train_step`` over a mesh of one,
    the state donated, the loss falling, the counts for the feed."""
    step, (p, o, t) = programs["step"], programs["step_state"]
    hvd.init()
    try:
        losses = []
        for _ in range(3):
            p, o, loss, counts = step(p, o, t)
            losses.append(float(loss))
        assert losses[2] < losses[1] < losses[0]
        assert counts.shape == (len(KEPT), HELD + 2)
        assert int(counts[:, -1].sum()) == 0        # dropped
    finally:
        hvd.shutdown()
