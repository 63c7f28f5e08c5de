"""GLM-4.7-Flash's layers (``horovod_tpu/models/glm_moe.py``) against
the plain float32 reference the chip benchmark keeps for them
(``benchmarks/chip/families/glm_moe_lm.py``), at a small size on the
CPU with seeded weights: the program in float32 must agree to rounding,
part by part and as a whole; the expert layer's shares add up to the
uncut layer; and routing drops nothing, whatever the load."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from .chip_bench import _paths  # noqa: F401  (makes chipbench importable)
from .compiled import beside, weights_under
from chipbench import check, harness, weights

from horovod_tpu.models import glm_moe, train_steps

pytestmark = pytest.mark.interpreter_of_its_own

FAMILY = harness.load_module("families", "glm_moe_lm")
CONFIG = {
    "vocab_size": 256, "num_hidden_layers": 2, "first_k_dense_replace": 1,
    "num_nextn_predict_layers": 1, "hidden_size": 32,
    "num_attention_heads": 2, "q_lora_rank": 12, "kv_lora_rank": 8,
    "qk_nope_head_dim": 6, "qk_rope_head_dim": 4, "v_head_dim": 10,
    "intermediate_size": 80, "moe_intermediate_size": 24,
    "n_routed_experts": 4, "num_experts_per_tok": 4,
    "routed_scaling_factor": 1.8, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
    "deployment": {"router_width": 16, "expert_offset": 4},
    "assumed": {"sequence_length": 16, "mtp_loss_weight": 0.3}}
SZ = FAMILY.sizes(CONFIG, 2)
REF = FAMILY.reference_fns(SZ)
TOL = dict(rtol=2e-5, atol=2e-6)


def float32(cfg, **changes):
    return dataclasses.replace(cfg, dtype=jnp.float32, **changes)


@pytest.fixture(scope="module")
def model():
    return glm_moe.GlmMoeLM(float32(FAMILY.build_model(SZ).cfg))


@pytest.fixture(scope="module")
def params():
    shapes, fans = FAMILY.param_shapes(SZ)
    return weights.make_tree(shapes, fans, seed=11, stream=0)["params"]


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.key(3), (2, SZ["seq"], SZ["d"]))


def positions(x):
    return jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])


def test_the_family_names_the_programs_own_parameters(model):
    program = harness.Program.__new__(harness.Program)
    program.family, program.sz, program.model = FAMILY, SZ, model
    program.shapes, _ = FAMILY.param_shapes(SZ)
    program._check_shapes()


def part(name, model, params, x):
    """(program, reference) of one part of the model on ``x``."""
    cfg = model.cfg
    moe = params["block_1"]
    if name == "mla":
        return (jax.jit(lambda p, x: glm_moe.LatentAttention(cfg).apply(
            {"params": p}, x, positions(x)))(moe["attn"], x),
            jax.jit(REF["mla"])(moe["attn"], x))
    if name == "expert_layer":
        return (jax.jit(lambda p, x: glm_moe.ExpertLayer(cfg).apply(
            {"params": p}, x)[0])(moe["moe"], x),
            jax.jit(REF["expert_layer"])(moe["moe"], x))
    dense = name == "dense_block"
    p = params["block_0"] if dense else moe
    return (jax.jit(lambda p, x: glm_moe.Block(cfg, use_moe=not dense).apply(
        {"params": p}, x, positions(x))[0])(p, x), jax.jit(REF["block"])(p, x))


@pytest.mark.parametrize("name", ["mla", "expert_layer", "dense_block",
                                  "expert_block"])
def test_a_part_of_the_program_is_the_references(name, model, params, x):
    got, want = part(name, model, params, x)
    np.testing.assert_allclose(got, want, **TOL)


def tokens():
    return FAMILY.make_batch(SZ, 2)(jax.random.key(5))[0]


@pytest.fixture(scope="module")
def programs(model, params):
    """The program's loss, counts and gradients, lowered at the file's
    start and compiled beside the tests of its parts
    (``tests/compiled.py``)."""
    return beside(loss_and_grads=jax.jit(jax.value_and_grad(
        train_steps.glm_moe_loss_fn(model), has_aux=True)).lower(
            params, tokens()))


def test_the_multi_token_module_reads_the_next_token_and_predicts_two_ahead(
        model, params, x):
    t = tokens()
    embedded = params["embed"]["embedding"][t]
    want = REF["mtp_hidden"](params["mtp"], x, embedded)
    got = jax.jit(lambda p, h, e: glm_moe.MultiTokenModule(model.cfg).apply(
        {"params": p}, h, jnp.roll(e, -1, axis=1), positions(h))[0])(
            params["mtp"], x, embedded)
    np.testing.assert_allclose(got, want, **TOL)
    head = params["lm_head"]["kernel"]
    from horovod_tpu.models.transformer import lm_loss_from_hidden
    np.testing.assert_allclose(
        lm_loss_from_hidden(got[:, :-1], head, t[:, 1:]),
        REF["head_loss"](head, want[:, :-2], t[:, 2:]), rtol=1e-5)


def test_the_whole_loss_and_its_gradients_are_the_references(programs,
                                                             params):
    """The reference's chain hands the embedding on as the second of a
    pair, so that the multi-token module's use of it reaches the
    embedding's gradient."""
    t = tokens()
    (loss, counts), grads = programs["loss_and_grads"](params, t)
    want_loss, _, want = check.StagedGradient(
        FAMILY.reference_stages(SZ))(params, {}, (t,))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    flat = lambda tree: weights.flat_shapes(
        jax.tree_util.tree_map(lambda a: (np.asarray(a),), tree))
    got, want = flat(grads), flat(want)
    assert set(got) == set(want)
    for path in want:
        np.testing.assert_allclose(
            got[path][0], want[path][0], rtol=2e-4,
            atol=2e-6 * float(np.abs(want[path][0]).max() + 1), err_msg=path)
    tok = t.size * SZ["top_k"]
    assert counts.shape == (3, SZ["experts_held"] + 2)
    assert counts[0].sum() == 0 and list(counts[1:].sum(axis=1)) == [tok, tok]


def test_the_shares_add_up_to_the_uncut_layer(model, x):
    """Every chip's routed part, plus the shared expert once, is the
    whole layer of the reference over all the router's experts."""
    e, held = SZ["experts"], SZ["experts_held"]
    whole = dict(SZ, experts_held=e, expert_offset=0)
    shapes, fans = FAMILY.param_shapes(whole)
    p = weights_under(shapes, fans, 13, 0, "params/block_1/moe")
    want = jax.jit(FAMILY.reference_fns(whole)["expert_layer"])(p, x)
    shared = jax.jit(FAMILY._swiglu)(p["shared"], x)
    total = shared
    for offset in range(0, e, held):
        mine = dict(p, experts={
            k: v[offset:offset + held] for k, v in p["experts"].items()})
        y, counts = jax.jit(glm_moe.ExpertLayer(
            float32(model.cfg, expert_offset=offset)).apply)(
                {"params": mine}, x)
        total = total + (y - shared)
        assert counts[glm_moe.DROPPED] == 0
    np.testing.assert_allclose(total, want, **TOL)


@pytest.mark.parametrize("chosen, held_each", [
    ((4, 13, 14, 15), 1),       # every token chooses one held expert
    ((0, 1, 14, 15), 0),        # none chooses any
    ((4, 5, 6, 7), 4),          # every choice is held: the whole buffer
])
def test_routing_drops_nothing_whatever_the_load(
        model, params, x, chosen, held_each):
    """The router's bias sends every token to ``chosen``; the held
    experts are 4 to 7. One held expert a token fills exactly the
    quarter tier of the row buffer; four need the whole of it."""
    p = jax.tree_util.tree_map(lambda a: a, params["block_1"]["moe"])
    bias = np.zeros(SZ["experts"], np.float32)
    bias[list(chosen)] = 10.0
    p["router"] = dict(p["router"], bias=jnp.asarray(bias))
    layer = glm_moe.ExpertLayer(model.cfg)
    reference = jax.jit(REF["expert_layer"])
    y, counts = jax.jit(layer.apply)({"params": p}, x)
    n = x.shape[0] * x.shape[1]
    assert counts[glm_moe.DROPPED] == 0
    assert counts[:SZ["experts_held"]].sum() == held_each * n
    assert counts[glm_moe.ABSENT] == (SZ["top_k"] - held_each) * n
    np.testing.assert_allclose(y, reference(p, x), **TOL)
    if not held_each:
        np.testing.assert_allclose(
            y, jax.jit(FAMILY._swiglu)(p["shared"], x), **TOL)
    grads = jax.jit(jax.grad(lambda q: jnp.sum(jnp.square(
        layer.apply({"params": q}, x)[0]))))(p)
    want = jax.jit(jax.grad(lambda q: jnp.sum(jnp.square(
        REF["expert_layer"](q, x)))))(p)
    for got, ref in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want)):
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-5)
