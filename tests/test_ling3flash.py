"""Ling-3.0-flash's layers (``horovod_tpu/models/ling3flash.py``) at a
small size on the CPU with seeded weights: Kimi delta attention against
a literal loop over positions, latent attention's head norms by hand
and through the flash kernels at the cell's two head sizes, the
group-limited choice against a literal loop over tokens, the four
shares of a layer adding up to the uncut layer with the shared expert
counted once, the whole model's loss and gradients against a plain
float32 reference written here and against the one the chip benchmark
keeps (``benchmarks/chip/families/ling3flash_lm.py``), the parameter
count by ISSUE 41's formulas at two sizes, and the kept layers under
their published indices. (Cold on this sandbox: 40 s.)"""

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
from horovod_tpu.models import glm_moe, ling3flash, train_steps
from horovod_tpu.parallel import flash_attention as fa

pytestmark = [pytest.mark.fast, pytest.mark.time_limit(120),
              pytest.mark.interpreter_of_its_own]

FAMILY = harness.load_module("families", "ling3flash_lm")
(D, HEADS, KD, RANK, NOPE, ROPE, VD, MLP, WIDTH, SHARED, EXPERTS, HELD,
 OFFSET, K, GROUPS, TOP_GROUPS, VOCAB, SEQ) = \
    32, 2, 8, 16, 8, 4, 8, 48, 16, 16, 16, 4, 8, 4, 4, 2, 64, 24
KEPT = (1, 2, 5)         # KDA + dense, KDA + experts, latent + experts
EPS, THETA, LOWER, SCALE, A_INIT, DT_INIT = 1e-6, 6e6, -5.0, 2.5, 0.0, -2.0
CONFIG = {
    "vocab_size": VOCAB, "num_hidden_layers": len(KEPT), "hidden_size": D,
    "published": {"num_hidden_layers": 42}, "layer_group_size": 6,
    "first_k_dense_replace": 2, "intermediate_size": MLP,
    "num_attention_heads": HEADS, "head_dim": KD,
    "short_conv_kernel_size": 4, "kda_lower_bound": LOWER,
    "kv_lora_rank": RANK, "qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE,
    "v_head_dim": VD, "rope_theta": THETA, "moe_intermediate_size": WIDTH,
    "moe_shared_expert_intermediate_size": SHARED, "num_experts": HELD,
    "num_experts_per_tok": K, "n_group": GROUPS, "topk_group": TOP_GROUPS,
    "routed_scaling_factor": SCALE, "rms_norm_eps": EPS,
    "kept_layers": list(KEPT),
    "deployment": {"router_width": EXPERTS, "expert_offset": OFFSET},
    "assumed": {"sequence_length": SEQ, "row_tier_headroom": 2.0,
                "gates": {"a_log_init": A_INIT, "dt_bias_init": DT_INIT}}}
SZ = FAMILY.sizes(CONFIG, 2)
TOL = dict(rtol=3e-5, atol=3e-6)


def in_float32(model):
    return ling3flash.Ling3FlashLM(
        dataclasses.replace(model.cfg, dtype=jnp.float32))


@pytest.fixture(scope="module")
def model():
    return in_float32(FAMILY.build_model(SZ))


@pytest.fixture(scope="module")
def params():
    shapes, fans = FAMILY.param_shapes(SZ)
    p = weights.make_tree(shapes, fans, seed=41, stream=0)["params"]
    # norm scales start at one and the bias at zero: seeded ones, so
    # that a scale left out, one shared where two are meant, or a bias
    # the choice does not read shows
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: 1.0 + 0.3 * jax.random.normal(
            jax.random.key(len(jax.tree_util.keystr(path))), leaf.shape)
        if path[-1].key == "scale" else 0.3 * jax.random.normal(
            jax.random.key(7), leaf.shape)
        if path[-1].key == "bias" else leaf, p)


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
        train_steps.ling3flash_train_step, model, params, t)
    return beside(
        loss_and_grads=jax.jit(jax.value_and_grad(
            train_steps.ling3flash_loss_fn(model), has_aux=True)).lower(
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
    """[B, S, H, R]: all of R, halves paired."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[1])[:, None] \
        * THETA ** (-jnp.arange(half) / half)
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def plain_kda(p, h):
    """Position by position: the state of a head decays a row (a key
    channel) at a time, is corrected by a rank-one term and read."""
    bt, seq = h.shape[:2]
    width = HEADS * KD
    z = jnp.pad(h @ p["in_proj_qkv"]["kernel"], ((0, 0), (3, 0), (0, 0)))
    qkv = jax.nn.silu(sum(z[:, j:j + seq] * p["conv"]["kernel"][j]
                          for j in range(4)))
    heads = lambda t: t.reshape(bt, seq, HEADS, KD)
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q = unit(heads(qkv[..., :width])) / math.sqrt(KD)
    k = unit(heads(qkv[..., width:2 * width]))
    v = heads(qkv[..., 2 * width:])
    f = heads(h @ p["in_proj_f"]["kernel"] + p["dt_bias"] + DT_INIT)
    g = LOWER * jax.nn.sigmoid(jnp.exp(p["A_log"] + A_INIT)[:, None] * f)
    bz = h @ p["in_proj_bz"]["kernel"]
    beta, gate = jax.nn.sigmoid(bz[..., :HEADS]), jax.nn.sigmoid(
        bz[..., HEADS:])
    state, outs = jnp.zeros((bt, HEADS, KD, KD)), []
    for t in range(seq):
        state = jnp.exp(g[:, t])[..., None] * state
        u = beta[:, t, :, None] * (
            v[:, t] - jnp.einsum("bhkv,bhk->bhv", state, k[:, t]))
        state = state + k[:, t][..., None] * u[..., None, :]
        outs.append(jnp.einsum("bhkv,bhk->bhv", state, q[:, t]))
    y = rms(jnp.stack(outs, 1), p["norm"]["scale"]) * gate[..., None]
    return y.reshape(bt, seq, width) @ p["out_proj"]["kernel"]


def plain_latent(p, h):
    seq = h.shape[1]
    q = jnp.einsum("bsd,dhe->bshe", h, p["q"]["kernel"])
    kv = h @ p["kv_a"]["kernel"]
    latent = rms(kv[..., :RANK], p["kv_norm"]["scale"])
    kv_b = jnp.einsum("bsr,rhe->bshe", latent, p["kv_b"]["kernel"])
    shared = jnp.repeat(kv[..., None, RANK:], HEADS, 2)
    k = jnp.concatenate([kv_b[..., :NOPE], shared], -1)
    q, k = rms(q, p["q_head_norm"]["scale"]), rms(k, p["k_head_norm"]["scale"])
    turn = lambda t: jnp.concatenate([t[..., :NOPE], rope(t[..., NOPE:])], -1)
    scores = jnp.einsum("bqhe,bkhe->bhqk", turn(q), turn(k)) \
        / math.sqrt(NOPE + ROPE)
    seen = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bkhe->bqhe", probs, kv_b[..., NOPE:]) \
        * jax.nn.sigmoid(h @ p["gate"]["kernel"])[..., None]
    return jnp.einsum("bqhe,hed->bqd", out, p["o"]["kernel"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def plain_weights(p, h, experts=EXPERTS, groups=GROUPS, top=TOP_GROUPS):
    """[..., experts]: sigmoids; a group's place by the sum of its two
    largest ``score + bias``; the four largest inside the ``top`` best
    groups; their scores over their sum, times 2.5."""
    s = jax.nn.sigmoid(h @ p["router"]["kernel"])
    biased = (s + p["router"]["bias"]).reshape(*s.shape[:-1], groups, -1)
    score = jnp.sort(biased, -1)[..., -2:].sum(-1)
    place = jnp.argsort(jnp.argsort(-score, -1), -1)
    inside = jnp.where((place < top)[..., None], biased, -jnp.inf)
    _, chosen = jax.lax.top_k(inside.reshape(s.shape), K)
    picked = s * jnp.sum(jax.nn.one_hot(chosen, experts), axis=-2)
    return SCALE * picked / jnp.sum(picked, -1, keepdims=True)


def plain_experts(p, h, held=HELD, offset=OFFSET, **routing):
    w = plain_weights(p, h, **routing)
    e, shared = p["experts"], p["shared"]
    return swiglu(h, *(shared[n]["kernel"] for n in ("gate", "up", "down"))) \
        + sum(w[..., offset + j, None] * swiglu(
            h, e["gate"][j], e["up"][j], e["down"][j]) for j in range(held))


def plain_loss(p, t):
    x = p["embed"]["embedding"][t]
    for i in KEPT:
        lp = p[f"layer_{i}"]
        h = rms(x, lp["norm1"]["scale"])
        x = x + (plain_latent if (i + 1) % 6 == 0 else plain_kda)(
            lp["mixer"], h)
        h = rms(x, lp["norm2"]["scale"])
        x = x + (swiglu(h, *(lp["mlp"][n]["kernel"]
                             for n in ("gate", "up", "down")))
                 if i < 2 else plain_experts(lp["moe"], h))
    logp = jax.nn.log_softmax(
        rms(x, p["norm_f"]["scale"])[:, :-1] @ p["lm_head"]["kernel"], -1)
    return -jnp.mean(jnp.take_along_axis(logp, t[:, 1:, None], -1))


# -- the tests ----------------------------------------------------------------

def test_the_family_names_the_programs_own_parameters(model):
    program = harness.Program.__new__(harness.Program)
    program.family, program.sz, program.model = FAMILY, SZ, model
    program.shapes, _ = FAMILY.param_shapes(SZ)
    program._check_shapes()


def cell_config():
    with open(os.path.join(_paths.BENCH, "configs",
                           "ling-3.0-flash-ep64-l7.json")) as f:
        return json.load(f)


def test_the_kept_layers_carry_their_published_indices():
    """Latent attention at 5, 11, ..., 41 of 42 and Kimi delta
    attention elsewhere; layers 0 and 1 dense; the cell's seven are the
    dense layer 1 and one whole period, each under its own index."""
    kinds = [ling3flash.layer_kind(i, 6) for i in range(42)]
    assert [i for i, k in enumerate(kinds) if k == "attention"] \
        == list(range(5, 42, 6))
    assert kinds.count("kda") == 35
    cell = FAMILY.sizes(cell_config(), 1)
    assert cell["kept"] == (1, 2, 3, 4, 5, 6, 7)
    assert FAMILY.kinds(cell) == [("kda", "dense")] + 3 * [
        ("kda", "experts")] + [("attention", "experts")] + 2 * [
        ("kda", "experts")]
    tree = FAMILY.program_shapes(FAMILY.build_model(cell), cell)["params"]
    assert sorted(k for k in tree if k.startswith("layer_")) \
        == [f"layer_{i}" for i in cell["kept"]]
    for i in cell["kept"]:
        assert ("kv_a" in tree[f"layer_{i}"]["mixer"]) == (i == 5)
        assert ("A_log" in tree[f"layer_{i}"]["mixer"]) == (i != 5)
        assert ("mlp" in tree[f"layer_{i}"]) == (i == 1)
        assert ("moe" in tree[f"layer_{i}"]) == (i != 1)
    assert tree["layer_5"]["mixer"]["q"]["kernel"].shape == (2560, 32, 192)
    assert "q_a" not in tree["layer_5"]["mixer"]         # q_lora_rank null
    assert tree["layer_2"]["mixer"]["in_proj_f"]["kernel"].shape \
        == (2560, 4096)                                  # no_kda_lora
    assert ling3flash.Ling3FlashConfig().layers == tuple(range(42))


@pytest.mark.parametrize("size", ["tiny", "the_cell"])
def test_the_parameter_count_is_the_formulas(size):
    """ISSUE 41's count by hand: a KDA mixer, the latent mixer, the
    dense SwiGLU, an expert layer's own, the two tables, the norms."""
    sz = SZ if size == "tiny" else FAMILY.sizes(cell_config(), 1)
    d, h, kd = sz["d"], sz["heads"], sz["kda_dim"]
    width, qk = h * kd, sz["nope"] + sz["rope"]
    kda = 3 * d * width + d * width + 2 * d * h + sz["conv"] * 3 * width \
        + width * d + h + width + kd          # A_log, dt_bias, the norm
    latent = d * h * qk + d * (sz["kv_rank"] + sz["rope"]) + sz["kv_rank"] \
        + sz["kv_rank"] * h * (sz["nope"] + sz["v_dim"]) + 2 * qk + d * h \
        + h * sz["v_dim"] * d
    experts = d * sz["experts"] + sz["experts"] + 3 * d * sz["shared_mlp"] \
        + sz["experts_held"] * 3 * d * sz["expert_mlp"]
    want = 2 * sz["vocab"] * d + d
    for mixer, ff in FAMILY.kinds(sz):
        want += (kda if mixer == "kda" else latent) + 2 * d \
            + (3 * d * sz["mlp"] if ff == "dense" else experts)
    tree = FAMILY.program_shapes(FAMILY.build_model(sz), sz)["params"]
    got = sum(math.prod(leaf.shape)
              for leaf in jax.tree_util.tree_leaves(tree))
    assert got == want == FAMILY.param_count(sz)
    if size == "the_cell":
        assert want == 822_036_800                  # ISSUE 41: "822.0M"
        assert kda == pytest.approx(52.65e6, rel=1e-3)
        assert latent == pytest.approx(31.96e6, rel=1e-3)
        assert 12 * want == pytest.approx(9.86e9, rel=1e-3)


def test_kimi_delta_attention_is_the_literal_loop(model, params):
    """The layer against the loop over positions written here: values
    and every leaf's gradient, the decay's own leaves (``in_proj_f``,
    ``dt_bias``, ``A_log``) among them; the gate is bounded."""
    p = params["layer_2"]["mixer"]
    x = jax.random.normal(jax.random.key(3), (2, SEQ, D))
    cot = jax.random.normal(jax.random.key(4), (2, SEQ, D))
    got, got_grads = out_and_vjp(
        lambda p, x: ling3flash.KimiDeltaAttention(model.cfg).apply(
            {"params": p}, x), cot, p, x)
    want, want_grads = out_and_vjp(plain_kda, cot, p, x)
    np.testing.assert_allclose(got, want, **TOL)
    got_grads, want_grads = flat(got_grads[0]), flat(want_grads[0])
    assert set(got_grads) == set(want_grads) >= {
        "A_log", "dt_bias", "in_proj_f/kernel", "in_proj_bz/kernel"}
    for path, w in want_grads.items():
        assert np.abs(w).max() > 0, path
        np.testing.assert_allclose(
            got_grads[path], w, rtol=2e-4,
            atol=1e-5 * float(np.abs(w).max() + 1), err_msg=path)
    assert p["in_proj_bz"]["kernel"].shape == (D, 2 * HEADS)    # head-wise
    assert p["norm"]["scale"].shape == (KD,) and p["A_log"].shape == (HEADS,)
    assert p["dt_bias"].shape == (HEADS * KD,)
    assert set(p["conv"]) == {"kernel"}                         # no bias


def test_the_gate_is_bounded_whatever_its_leaves_hold(model, params,
                                                      monkeypatch):
    """``g = lower sigmoid(exp(A_log) f)`` stays in [lower, 0] for any
    leaves: what the rule's kernels count on."""
    seen = {}

    def capture(q, k, v, g, beta):
        seen.update(g=g, q=q, k=k)
        return jnp.zeros_like(v)

    monkeypatch.setattr(ling3flash, "kimi_delta_attention", capture)
    p = jax.tree_util.tree_map(lambda a: 30.0 * a, params["layer_2"]["mixer"])
    x = jax.random.normal(jax.random.key(6), (2, SEQ, D))
    ling3flash.KimiDeltaAttention(model.cfg).apply({"params": p}, x)
    g = np.asarray(seen["g"])
    assert g.shape == (2, SEQ, HEADS, KD) and g.dtype == np.float32
    assert g.min() >= LOWER and g.max() <= 0.0 and g.min() < -4.9
    # a head the SiLU left all but zero is shorter than a unit
    for name, length in (("k", 1.0), ("q", KD ** -0.5)):
        norms = np.linalg.norm(seen[name], axis=-1)
        assert norms.max() <= length * 1.001
        assert np.median(norms) == pytest.approx(length, rel=1e-3)


def test_q_and_the_assembled_k_are_normed_a_head_at_a_time(model, params,
                                                           monkeypatch):
    """What reaches the attention call: ``W_q h`` and ``[k_nope |
    k_rope]`` over each head's own mean square, each times one weight
    vector of ``nope + rope``; then the rotary on the last ``rope``
    entries alone (position 0 is turned by nothing); v is narrower than
    the score head and untouched."""
    p = params["layer_5"]["mixer"]
    assert p["q_head_norm"]["scale"].shape == (NOPE + ROPE,) \
        == p["k_head_norm"]["scale"].shape
    assert p["gate"]["kernel"].shape == (D, HEADS)
    assert "q_a" not in p and p["q"]["kernel"].shape == (D, HEADS, NOPE + ROPE)
    x = jax.random.normal(jax.random.key(6), (2, SEQ, D))
    seen = {}

    def capture(q, k, v, causal):
        seen.update(q=q, k=k, v=v)
        return fa._dense_reference(q, k, v, True, 0, 0)

    monkeypatch.setattr(glm_moe, "best_attention", capture)
    pos = jnp.broadcast_to(jnp.arange(SEQ)[None], (2, SEQ))
    got = glm_moe.LatentAttention(model.cfg).apply({"params": p}, x, pos)
    raw_q = np.einsum("bsd,dhe->bshe", x, p["q"]["kernel"])
    kv = np.asarray(x @ p["kv_a"]["kernel"])
    latent = np.asarray(rms(kv[..., :RANK], p["kv_norm"]["scale"]))
    kv_b = np.einsum("bsr,rhe->bshe", latent, p["kv_b"]["kernel"])
    raw_k = np.concatenate(
        [kv_b[..., :NOPE], np.repeat(kv[..., None, RANK:], HEADS, 2)], -1)
    for name, raw in (("q", raw_q), ("k", raw_k)):
        by_hand = raw / np.sqrt((raw ** 2).mean(-1, keepdims=True) + EPS) \
            * np.asarray(p[f"{name}_head_norm"]["scale"])
        np.testing.assert_allclose(seen[name][:, 0], by_hand[:, 0], **TOL)
        np.testing.assert_allclose(seen[name][..., :NOPE],
                                   by_hand[..., :NOPE], **TOL)
        assert float(np.abs(seen[name][:, 1:, :, NOPE:]
                            - by_hand[:, 1:, :, NOPE:]).max()) > 1e-3
    assert seen["v"].shape == (2, SEQ, HEADS, VD)
    np.testing.assert_allclose(seen["v"], kv_b[..., NOPE:], **TOL)
    np.testing.assert_allclose(got, jax.jit(plain_latent)(p, x), **TOL)


def test_latent_attention_runs_through_the_flash_kernels_at_192_over_128(
        monkeypatch):
    """32 heads, a score head of 128 + 64 and a value head of 128: the
    kernels in interpret mode against the reference's dense softmax a
    block of queries at a time."""
    config = dict(CONFIG, hidden_size=256, num_attention_heads=32,
                  kv_lora_rank=64, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128,
                  assumed=dict(CONFIG["assumed"], sequence_length=32))
    sz = FAMILY.sizes(config, 1)
    shapes, fans = FAMILY.param_shapes(sz)
    p = weights_under(shapes, fans, 7, 0, "params/layer_5/mixer")
    calls = []

    def through_kernels(q, k, v, causal):
        calls.append((q.shape, k.shape, v.shape))
        return fa.flash_attention(q, k, v, causal=True, block_q=16,
                                  block_k=16, interpret=True)

    monkeypatch.setattr(glm_moe, "best_attention", through_kernels)
    cfg = dataclasses.replace(FAMILY.build_model(sz).cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(8), (1, 32, 256))
    got = jax.jit(glm_moe.LatentAttention(cfg).apply)(
        {"params": p}, x, jnp.arange(32)[None])
    assert calls == [((1, 32, 32, 192), (1, 32, 32, 192), (1, 32, 32, 128))]
    np.testing.assert_allclose(
        got, jax.jit(FAMILY.reference_fns(sz)["attention"])(p, x),
        rtol=1e-4, atol=1e-5)


def literal_choice(biased, groups, top, k):
    """Token by token on the host."""
    chosen = []
    for row in np.asarray(biased):
        by_group = row.reshape(groups, -1)
        score = np.sort(by_group, -1)[:, -2:].sum(-1)
        best = np.argsort(-score, kind="stable")[:top]
        inside = np.full_like(by_group, -np.inf)
        inside[best] = by_group[best]
        chosen.append(np.argsort(-inside.reshape(-1), kind="stable")[:k])
    return np.sort(np.array(chosen), -1)


def test_the_group_limited_choice_is_the_literal_loop(model, params):
    """Program (what the layer sows), the benchmark's reference and the
    reference written here against a loop over tokens: the same four
    experts a token, inside two of the four groups."""
    p = params["layer_2"]["moe"]
    h = jax.random.normal(jax.random.key(11), (2, SEQ, D))
    biased = jax.nn.sigmoid(h.reshape(-1, D) @ p["router"]["kernel"]) \
        + p["router"]["bias"]
    want = literal_choice(biased, GROUPS, TOP_GROUPS, K)
    assert all(len(set(row // (EXPERTS // GROUPS))) <= TOP_GROUPS
               for row in want)
    (_, _), state = jax.jit(lambda p, h: glm_moe.ExpertLayer(model.cfg).apply(
        {"params": p}, h, mutable=["intermediates"]))(p, h)
    np.testing.assert_array_equal(
        np.sort(state["intermediates"]["chosen"][0], -1), want)
    weights_, theirs = jax.jit(FAMILY.reference_fns(SZ)["routing"])(
        p, h.reshape(-1, D))
    np.testing.assert_array_equal(np.sort(theirs, -1), want)
    mine = np.asarray(plain_weights(p, h.reshape(-1, D)))
    np.testing.assert_allclose(weights_, mine, **TOL)
    np.testing.assert_array_equal(
        np.sort(np.argsort(-mine, -1, kind="stable")[:, :K], -1), want)
    np.testing.assert_allclose(mine.sum(-1), SCALE, rtol=1e-5)
    free = jax.lax.top_k(biased, K)[1]
    assert (np.sort(free, -1) != want).any()     # the groups do limit it


def test_the_shares_add_up():
    """Router width 16 over 4 shares of 4: every share routes over all
    16 with the groups, computes its held experts' part and the whole
    shared expert; the four outputs less three shared experts are the
    uncut layer, and every assignment is held by exactly one share."""
    whole = dataclasses.replace(
        FAMILY.build_model(SZ).cfg, dtype=jnp.float32,
        experts_held=EXPERTS, expert_offset=0)
    x = jax.random.normal(jax.random.key(1), (2, SEQ, D))
    p = jax.jit(glm_moe.ExpertLayer(whole).init)(
        jax.random.key(2), x)["params"]
    p["router"]["bias"] = 0.3 * jax.random.normal(jax.random.key(3),
                                                  (EXPERTS,))

    @jax.jit
    def shares(p, x):
        out = []
        for offset in range(0, EXPERTS, HELD):
            mine = dict(p, experts={k: v[offset:offset + HELD]
                                    for k, v in p["experts"].items()})
            cfg = dataclasses.replace(whole, experts_held=HELD,
                                      expert_offset=offset)
            out.append(glm_moe.ExpertLayer(cfg).apply({"params": mine}, x))
        shared = swiglu(x, *(p["shared"][n]["kernel"]
                             for n in ("gate", "up", "down")))
        return out, shared, glm_moe.ExpertLayer(whole).apply(
            {"params": p}, x)

    parts, shared, (uncut, uncut_counts) = shares(p, x)
    total, seen = -3.0 * shared, 0
    for y, counts in parts:
        assert counts.shape == (HELD + 2,) and counts[glm_moe.DROPPED] == 0
        assert int(counts.sum()) == 2 * SEQ * K
        total = total + y
        seen += int(counts[:HELD].sum())
    assert seen == 2 * SEQ * K == int(uncut_counts[:EXPERTS].sum())
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        uncut, jax.jit(lambda p, x: plain_experts(
            p, x, held=EXPERTS, offset=0))(p, x), rtol=1e-4, atol=1e-5)


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
    """``ling3flash_train_step``: ``_counted_train_step`` over a mesh
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
