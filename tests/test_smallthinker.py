"""SmallThinker-21BA3B's layers (``horovod_tpu/models/smallthinker.py``)
at a small size on the CPU with seeded weights: the window and the
rotary by published index, each layout read for itself; the router's
work in the traced block before the attention call and on the block's
input; the whole model's loss and gradients against a plain float32
reference written here and against the one the chip benchmark keeps
(``benchmarks/chip/families/smallthinker_lm.py``); the four shares of a
layer adding up to the uncut reference's; the parameter count by ISSUE
49's formulas at two sizes; the step on the counted path. (Cold on this
sandbox: 30 s.)"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .chip_bench import _paths  # noqa: F401  (makes chipbench importable)
from .compiled import beside, step_on_a_mesh_of_one
from chipbench import check, harness, weights

import horovod_tpu.jax as hvd
from horovod_tpu.models import glm_moe, smallthinker, train_steps
from horovod_tpu.parallel import flash_attention as fa

pytestmark = [pytest.mark.fast, pytest.mark.time_limit(120),
              pytest.mark.interpreter_of_its_own]

FAMILY = harness.load_module("families", "smallthinker_lm")
D, HEADS, KV, HD, WIDTH, EXPERTS, HELD, OFFSET, K, VOCAB, SEQ, WINDOW = \
    32, 7, 1, 8, 16, 16, 4, 8, 3, 64, 24, 8
KEPT = (0, 1)            # full without positions, windowed with the rotary
EPS, THETA = 1e-6, 1.5e6
CONFIG = {
    "vocab_size": VOCAB, "num_hidden_layers": len(KEPT), "hidden_size": D,
    "head_dim": HD, "num_attention_heads": HEADS, "num_key_value_heads": KV,
    "sliding_window_layout": list(smallthinker.PUBLISHED_LAYOUT),
    "rope_layout": list(smallthinker.PUBLISHED_LAYOUT),
    "sliding_window_size": WINDOW, "rope_theta": THETA, "rope_scaling": None,
    "moe_ffn_hidden_size": WIDTH, "moe_num_primary_experts": HELD,
    "moe_num_active_primary_experts": K,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "tie_word_embeddings": False, "rms_norm_eps": EPS,
    "kept_layers": list(KEPT),
    "deployment": {"router_width": EXPERTS, "expert_offset": OFFSET},
    "assumed": {"sequence_length": SEQ}}
SZ = FAMILY.sizes(CONFIG, 2)
TOL = dict(rtol=3e-5, atol=3e-6)


def in_float32(model, **over):
    return smallthinker.SmallThinkerLM(dataclasses.replace(
        model.cfg, dtype=jnp.float32, **over))


@pytest.fixture(scope="module")
def model():
    return in_float32(FAMILY.build_model(SZ))


@pytest.fixture(scope="module")
def params():
    shapes, fans = FAMILY.param_shapes(SZ)
    p = weights.make_tree(shapes, fans, seed=49, stream=0)["params"]
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
    with the state it is to train (``step_state``: donated to its first
    call). The step is a program of its own, not the first plus an
    update: ``shard_map`` over the mesh, the distributed optimizer's
    exchange, the state donated."""
    t = tokens()
    step, state = step_on_a_mesh_of_one(
        train_steps.smallthinker_train_step, model, params, t)
    return beside(
        loss_and_grads=jax.jit(jax.value_and_grad(
            train_steps.smallthinker_loss_fn(model), has_aux=True)).lower(
                params, t),
        plain=jax.jit(jax.value_and_grad(plain_loss)).lower(params, t),
        step=step, step_state=state)


def flat(tree):
    return {k: v[0] for k, v in weights.flat_shapes(
        jax.tree_util.tree_map(lambda a: (np.asarray(a),), tree)).items()}


def cell_config():
    with open(os.path.join(_paths.BENCH, "configs",
                           "smallthinker-21b-a3b-ep4-l4.json")) as f:
        return json.load(f)


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


def plain_attention(p, h, windowed, rotary):
    q = jnp.einsum("bsd,dhe->bshe", h, p["q"]["kernel"])
    k = jnp.einsum("bsd,dhe->bshe", h, p["k"]["kernel"])
    v = jnp.einsum("bsd,dhe->bshe", h, p["v"]["kernel"])
    if rotary:
        q, k = rope(q), rope(k)
    k, v = (jnp.repeat(t, HEADS // KV, 2) for t in (k, v))
    scores = jnp.einsum("bqhe,bkhe->bhqk", q, k) / math.sqrt(HD)
    behind = jnp.arange(h.shape[1])[:, None] - jnp.arange(h.shape[1])[None]
    seen = (behind >= 0) & ((behind < WINDOW) if windowed else True)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhe,hed->bqd", probs, v, p["o"]["kernel"])


def plain_experts(p, u, r, held=HELD, offset=OFFSET):
    """The three largest of the logits ``r W_r``; a softmax over those
    three; the held experts' dense masked sum of ``W_down (relu(W_gate
    u) * (W_up u))``; no shared expert."""
    top, chosen = jax.lax.top_k(r @ p["router"]["kernel"], K)
    w = jnp.sum(jax.nn.one_hot(chosen, EXPERTS)
                * jax.nn.softmax(top, -1)[..., None], axis=-2)
    e = p["experts"]
    return sum(w[..., offset + j, None] * (
        (jax.nn.relu(u @ e["gate"][j]) * (u @ e["up"][j])) @ e["down"][j])
        for j in range(held))


def plain_loss(p, t):
    x = p["embed"]["embedding"][t]
    for i in KEPT:
        lp = p[f"layer_{i}"]
        h = x + plain_attention(
            lp["attention"], rms(x, lp["attention_norm"]["scale"]),
            windowed=i % 4 != 0, rotary=i % 4 != 0)
        x = h + plain_experts(lp["moe"], rms(h, lp["ffn_norm"]["scale"]), x)
    logp = jax.nn.log_softmax(
        rms(x, p["norm_f"]["scale"])[:, :-1] @ p["lm_head"]["kernel"], -1)
    return -jnp.mean(jnp.take_along_axis(logp, t[:, 1:, None], -1))


# -- the tests ----------------------------------------------------------------

def test_the_family_names_the_programs_own_parameters(model):
    program = harness.Program.__new__(harness.Program)
    program.family, program.sz, program.model = FAMILY, SZ, model
    program.shapes, _ = FAMILY.param_shapes(SZ)
    program._check_shapes()


def test_the_kept_layers_carry_their_published_indices():
    """Neither a window nor a rotary at 0, 4, ..., 48 of 52 and both
    elsewhere; the cell's four are one whole period, each under its own
    index in the parameter tree, every one an expert layer."""
    layout = smallthinker.PUBLISHED_LAYOUT
    assert len(layout) == 52 and layout.count(0) == 13
    assert [i for i, on in enumerate(layout) if not on] \
        == list(range(0, 52, 4))
    cell = FAMILY.sizes(cell_config(), 2)
    assert cell["kept"] == (0, 1, 2, 3)
    assert cell["window_layout"] == cell["rope_layout"] == layout
    assert FAMILY.expert_layers(cell) == 4
    tree = FAMILY.program_shapes(FAMILY.build_model(cell), cell)["params"]
    assert sorted(k for k in tree if k.startswith("layer_")) \
        == [f"layer_{i}" for i in cell["kept"]]
    for i in cell["kept"]:
        assert set(tree[f"layer_{i}"]) == {"attention_norm", "attention",
                                           "ffn_norm", "moe"}
        assert set(tree[f"layer_{i}"]["moe"]) == {"router", "experts"}
        assert set(tree[f"layer_{i}"]["moe"]["router"]) == {"kernel"}
    assert smallthinker.SmallThinkerConfig().layers == tuple(range(52))


@pytest.mark.parametrize("size", ["tiny", "the_cell"])
def test_the_parameter_count_is_the_formulas(size):
    """ISSUE 49's count by hand: attention, two norms, the router, the
    held experts; the embedding, the untied head, the final norm."""
    sz = SZ if size == "tiny" else FAMILY.sizes(cell_config(), 2)
    d, hd = sz["d"], sz["head_dim"]
    outside = d * sz["heads"] * hd + 2 * d * sz["kv_heads"] * hd \
        + sz["heads"] * hd * d + 2 * d + d * sz["experts"]
    expert = 3 * d * sz["expert_mlp"]
    want = len(sz["kept"]) * (outside + sz["experts_held"] * expert) \
        + 2 * sz["vocab"] * d + d
    tree = FAMILY.program_shapes(FAMILY.build_model(sz), sz)["params"]
    got = sum(math.prod(leaf.shape)
              for leaf in jax.tree_util.tree_leaves(tree))
    assert got == want == FAMILY.param_count(sz)
    if size == "the_cell":
        assert (outside, expert) == (21_140_480, 5_898_240)
        assert want == 559_290_880


@pytest.mark.parametrize("window_on, rotary_on", [(0, 0), (1, 1), (0, 1),
                                                  (1, 0)])
def test_the_window_and_the_rotary_are_read_each_from_its_own_layout(
        model, params, monkeypatch, window_on, rotary_on):
    """Layer 1 under layouts that need not agree: what reaches the
    attention call has the window where ``sliding_window_layout`` says
    so and turned q and k where ``rope_layout`` does (position 0 is
    turned by nothing; v never); no norm on q or k; the result is the
    dense masked softmax."""
    cfg = dataclasses.replace(
        model.cfg, sliding_window_layout=(0, window_on),
        rope_layout=(0, rotary_on))
    p = params["layer_1"]["attention"]
    x = jax.random.normal(jax.random.key(6), (2, SEQ, D))
    seen = {}

    def capture(q, k, v, window=None):
        seen.update(q=q, k=k, v=v, window=window)
        return fa._dense_reference(q, k, v, True, 0, 0, window)

    monkeypatch.setattr(smallthinker, "best_grouped_attention", capture)
    pos = jnp.broadcast_to(jnp.arange(SEQ)[None], (2, SEQ))
    got = smallthinker.LocalGlobalAttention(cfg, 1).apply(
        {"params": p}, x, pos)
    assert seen["window"] == (WINDOW if window_on else None)
    assert set(p) == {"q", "k", "v", "o"}
    for name, heads in (("q", HEADS), ("k", KV), ("v", KV)):
        raw = np.einsum("bsd,dhe->bshe", x, p[name]["kernel"])
        assert seen[name].shape == (2, SEQ, heads, HD)
        np.testing.assert_allclose(seen[name][:, 0], raw[:, 0], **TOL)
        turned = float(np.abs(seen[name][:, 1:] - raw[:, 1:]).max()) > 1e-3
        assert turned == bool(rotary_on and name != "v")
    np.testing.assert_allclose(
        got, jax.jit(plain_attention, static_argnums=(2, 3))(
            p, x, bool(window_on), bool(rotary_on)), **TOL)


def test_the_routers_work_stands_before_the_attention_call(model, params,
                                                           monkeypatch):
    """The traced block: the router's call (``route``) and the plan of
    the way to the experts (``span_of``) come before the attention
    call, which comes before the experts' products; and the router read
    the block's input, not anything attention made."""
    def attention_call(q, k, v, window=None):
        return jax.jit(lambda q, k, v: fa._dense_reference(
            q, k, v, True, 0, 0, window)).__call__(q, k, v)

    seen = []
    real_route = glm_moe.route

    def noting_route(xf, w_r, bias, cfg):
        seen.append(xf)
        return real_route(xf, w_r, bias, cfg)

    monkeypatch.setattr(glm_moe, "route", noting_route)
    monkeypatch.setattr(smallthinker, "best_grouped_attention",
                        attention_call)
    x = jax.random.normal(jax.random.key(7), (2, SEQ, D))
    pos = jnp.broadcast_to(jnp.arange(SEQ)[None], (2, SEQ))
    jaxpr = jax.make_jaxpr(smallthinker.Block(model.cfg, 1).apply)(
        {"params": params["layer_1"]}, x, pos)
    order = [str(e.params.get("name", e.primitive.name))
             for e in jaxpr.jaxpr.eqns]
    at = {name: order.index(name) for name in
          ("route", "argsort", "span_of", "<lambda>", "cond")}
    # (the experts' products run inside the conditional over the row
    # buffer's tiers)
    assert at["route"] < at["argsort"] < at["span_of"] < at["<lambda>"] \
        < at["cond"]
    assert order.count("span_of") == 1 == order.count("route")
    # the rows the router read are the block's input, reshaped
    (rows,) = seen
    assert rows.shape == (2 * SEQ, D)
    first = jaxpr.jaxpr.eqns[0]
    assert first.primitive.name == "reshape" \
        and first.invars[0] is jaxpr.jaxpr.invars[-2]


def test_the_whole_loss_and_its_gradients_are_the_plain_references(
        programs, params):
    """Against the reference written in this file, and against the chip
    benchmark's, stage by stage as ``check.py`` calls it: loss, counts
    and every leaf's gradient."""
    t = tokens()
    (loss, counts), grads = programs["loss_and_grads"](params, t)
    want_loss, want = programs["plain"](params, t)
    with jax.default_matmul_precision("highest"):
        theirs_loss, _, theirs = check.StagedGradient(
            FAMILY.reference_stages(SZ))(params, {}, (t,))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    np.testing.assert_allclose(theirs_loss, want_loss, rtol=1e-6)
    assert counts.shape == (len(KEPT), HELD + 2)
    assert np.asarray(counts).sum(axis=1).tolist() == [t.size * K] * 2
    got, want, theirs = flat(grads), flat(want), flat(theirs)
    assert set(got) == set(want) == set(theirs)
    for path in want:
        for mine in (got[path], theirs[path]):
            np.testing.assert_allclose(
                mine, want[path], rtol=3e-4,
                atol=3e-6 * float(np.abs(want[path]).max() + 1),
                err_msg=path)
    for i in KEPT:
        assert got[f"layer_{i}/moe/router/kernel"].any()


def test_the_four_shares_add_up_to_the_uncut_references_layer(model):
    """Four chips of four experts each (offsets 0, 4, 8, 12 of 16: the
    cell's 0, 16, 32, 48 of 64), every one routing over all 16 on the
    router's own rows: their parts add up to what the benchmark's
    reference gives for the whole layer of 16."""
    whole = dataclasses.replace(model.cfg, experts_held=EXPERTS,
                                expert_offset=0)
    u = jax.random.normal(jax.random.key(11), (2, SEQ, D))
    r = jax.random.normal(jax.random.key(12), (2, SEQ, D))
    p = jax.jit(glm_moe.ExpertLayer(whole).init)(jax.random.key(13), u)[
        "params"]

    @jax.jit
    def shares(p, u, r):
        out = []
        for offset in range(0, EXPERTS, HELD):
            cfg = dataclasses.replace(whole, experts_held=HELD,
                                      expert_offset=offset)
            mine = {"router": p["router"], "experts": {
                k: v[offset:offset + HELD]
                for k, v in p["experts"].items()}}
            out.append(glm_moe.ExpertLayer(cfg).apply({"params": mine}, u, r))
        return out

    parts = shares(p, u, r)
    assert sum(int(c[:HELD].sum()) for _, c in parts) == u.shape[0] * SEQ * K
    want = jax.jit(lambda p, u, r: FAMILY.reference_fns(SZ)["expert_layer"](
        p, u, r, held=EXPERTS, offset=0))(p, u, r)
    np.testing.assert_allclose(sum(y for y, _ in parts), want, atol=1e-5)
    # a share alone is not the layer
    assert float(jnp.abs(parts[0][0] - want).max()) > 1e-3


def test_the_step_trains_on_the_counted_path(programs):
    """``smallthinker_train_step``: ``_counted_train_step`` over a mesh
    of one, the state donated, the loss falling, the counts for the
    feed."""
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
