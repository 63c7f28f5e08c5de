"""Olmo-Hybrid's layers (``horovod_tpu/models/olmo_hybrid.py``) against
the plain float32 reference the chip benchmark keeps for them
(``benchmarks/chip/families/olmo_hybrid_lm.py``), at a small size on the
CPU with seeded weights: published layers 2 (Gated DeltaNet, a key head
of 12 over a value head of 24, ``beta`` to 2) and 3 (full attention, no
positional signal), a dense SwiGLU behind output norms in each. The
program in float32, **its heads laid out as the chip lays them**, must
agree to rounding in the loss and in every leaf's gradient; the counted
step, bfloat16 activations over a mesh of one, within bfloat16's reach
of the same reference. (Cold on this sandbox: 38 s.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu.jax as hvd

from .chip_bench import _paths  # noqa: F401  (makes chipbench importable)
from .compiled import beside, step_on_a_mesh_of_one
from chipbench import check, harness, weights

from horovod_tpu.models import olmo_hybrid, qwen3next, train_steps

pytestmark = [pytest.mark.fast, pytest.mark.time_limit(170),
              pytest.mark.interpreter_of_its_own]

FAMILY = harness.load_module("families", "olmo_hybrid_lm")
KEPT = [2, 3]
CONFIG = {
    "vocab_size": 128, "num_hidden_layers": 2, "hidden_size": 32,
    "intermediate_size": 48, "num_attention_heads": 2,
    "layer_types": list(olmo_hybrid.PUBLISHED_LAYER_TYPES),
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 12, "linear_value_head_dim": 24,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rms_norm_eps": 1e-6,
    "published": {"num_hidden_layers": 32}, "kept_layers": KEPT,
    "assumed": {"sequence_length": 40, "head_dim": 16,
                "gates": {"a_log_init": 2.08, "dt_bias_init": -4.6}}}
SZ = FAMILY.sizes(CONFIG, 2)
REF = FAMILY.reference_fns(SZ)
LANES = 16      # a run of 12 columns behind 4 zeros, as the chip's 96 in 128


@pytest.fixture(scope="module")
def params():
    shapes, fans = FAMILY.param_shapes(SZ)
    return weights.make_tree(shapes, fans, seed=21, stream=0)["params"]


@pytest.fixture(scope="module")
def tokens():
    return FAMILY.make_batch(SZ, 2)(jax.random.key(5))[0]


@pytest.fixture(scope="module")
def reference(params, tokens):
    """The reference's loss and gradients, its stages compiled once."""
    with jax.default_matmul_precision("highest"):
        loss, _, grads = check.StagedGradient(
            FAMILY.reference_stages(SZ))(params, {}, (tokens,))
    return float(loss), grads


@pytest.fixture(scope="module")
def programs(params, tokens):
    """The file's two whole-model programs, lowered at its start and
    compiled beside one another and beside the reference's stages
    (``tests/compiled.py``): the float32 program's loss and gradients
    with its delta rule's heads laid out to ``LANES`` (the interpreter
    would take them as they come; ``told``: what the rule was told as
    it was traced), and the step on the counted path in bfloat16 with
    the state it is to train (``step_state``). The step is a program
    of its own, not the first plus an update: bfloat16 activations,
    ``shard_map`` over the mesh, the distributed optimizer's exchange,
    the state donated."""
    told = []
    rule = qwen3next.gated_delta_rule

    def telling(*args, **kwargs):
        told.append(([a.shape for a in args], kwargs))
        return rule(*args, **kwargs)

    model = olmo_hybrid.OlmoHybridLM(dataclasses.replace(
        FAMILY.build_model(SZ).cfg, dtype=jnp.float32))
    fn = jax.jit(jax.value_and_grad(train_steps.olmo_hybrid_loss_fn(model)))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(qwen3next, "head_lanes", lambda: LANES)
        patch.setattr(qwen3next, "gated_delta_rule", telling)
        laid_out = fn.lower(params, tokens)
    step, state = step_on_a_mesh_of_one(
        train_steps.olmo_hybrid_train_step, FAMILY.build_model(SZ), params,
        tokens)
    return beside(laid_out=laid_out, told=told, step=step, step_state=state)


@pytest.fixture(scope="module")
def laid_out(programs, params, tokens):
    """The laid-out program's loss and gradients on the seeded weights,
    what the rule was told, and the compiled function for further
    parameters."""
    fn = programs["laid_out"]
    loss, grads = fn(params, tokens)
    return float(loss), grads, programs["told"], fn


def flat(tree):
    return {k: v[0] for k, v in weights.flat_shapes(
        jax.tree_util.tree_map(lambda a: (np.asarray(a),), tree)).items()}


def test_the_family_names_the_programs_own_parameters():
    program = harness.Program.__new__(harness.Program)
    program.family, program.sz = FAMILY, SZ
    program.model = FAMILY.build_model(SZ)
    program.shapes, _ = FAMILY.param_shapes(SZ)
    program._check_shapes()
    assert FAMILY.kinds(SZ) == ["delta", "attention"]
    assert FAMILY.kinds(dict(SZ, kept=tuple(range(32)))).count("delta") == 24


def test_the_whole_loss_and_its_gradients_are_the_references(laid_out,
                                                             reference):
    loss, grads, _, _ = laid_out
    want_loss, want = reference
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    got, want = flat(grads), flat(want)
    assert set(got) == set(want)
    for path in want:
        np.testing.assert_allclose(
            got[path], want[path], rtol=3e-4,
            atol=3e-6 * float(np.abs(want[path]).max() + 1), err_msg=path)


def test_the_rule_gets_laid_out_heads_and_a_beta_that_passes_one(laid_out,
                                                                 params, tokens):
    """One linear layer, traced once: q and k [B, S, 2, 16], v
    [B, S, 2, 32] (a run of 12 columns in 16 lanes, a value head two
    runs), ``beta_max`` 2 and the widths that are no padding for the
    gauge. On the seeded weights ``beta = 2 sigmoid(x W_b)`` does pass
    1 at some position of every head."""
    _, _, told, _ = laid_out
    (shapes, kwargs), = told
    assert shapes[:3] == [(2, 40, 2, 16), (2, 40, 2, 16), (2, 40, 2, 32)]
    assert kwargs == {"beta_max": 2.0, "filled": (12, 24)}
    x = params["embed"]["embedding"][tokens]     # the first kept layer's
    beta = 2.0 * jax.nn.sigmoid(
        x @ params["layer_2"]["mixer"]["in_proj_ba"]["kernel"][:, :2])
    assert float(beta.min()) > 0 and (np.asarray(beta.max((0, 1))) > 1).all()


def test_the_blocks_norms_sit_on_the_outputs(laid_out, params, tokens):
    """No sub-layer has a norm on its input, and each has one on its
    output: with both scales of both blocks at zero the residual passes
    through unchanged, whatever the mixers and the feed-forwards hold
    (a block with norms on the inputs would still add their outputs),
    and the loss is the head's on the embedding alone."""
    _, _, _, fn = laid_out
    for i in KEPT:
        assert set(params[f"layer_{i}"]) == {
            "mixer", "mixer_norm", "mlp", "mlp_norm"}
    silent = dict(params, **{f"layer_{i}": dict(
        params[f"layer_{i}"],
        mixer_norm={"scale": jnp.zeros((SZ["d"],))},
        mlp_norm={"scale": jnp.zeros((SZ["d"],))}) for i in KEPT})
    loss, _ = fn(silent, tokens)
    hidden = FAMILY._rms(params["embed"]["embedding"][tokens],
                         params["norm_f"], SZ["eps"])
    np.testing.assert_allclose(loss, REF["head_loss"](
        params["lm_head"]["kernel"], hidden[:, :-1], tokens[:, 1:]),
        rtol=1e-6)


def test_full_attention_carries_no_positional_signal(params):
    """A causal layer without one: the last position's output does not
    change when the positions before it are permuted."""
    p = params["layer_3"]["mixer"]
    cfg = dataclasses.replace(FAMILY.build_model(SZ).cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(3), (1, SZ["seq"], SZ["d"]))
    mixed = x.at[:, :-1].set(x[:, :-1][:, ::-1])
    run = jax.jit(lambda x: olmo_hybrid.FullAttention(cfg).apply(
        {"params": p}, x))
    np.testing.assert_allclose(run(mixed)[:, -1], run(x)[:, -1], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(run(x), jax.jit(REF["attention"])(p, x),
                               rtol=3e-5, atol=3e-6)


def test_the_step_trains_on_the_counted_path_in_bfloat16(programs, params,
                                                         reference):
    """``olmo_hybrid_train_step``: ``_loss_train_step`` over a mesh of
    one, bfloat16 activations, the state donated, the loss falling; its
    first loss and its first update (the gradient: the momentum starts
    at zero) within bfloat16's reach of the float32 reference, leaf by
    leaf."""
    want_loss, want = reference
    step, (p, o, t) = programs["step"], programs["step_state"]
    hvd.init()
    try:
        losses = []
        for i in range(3):
            p, o, loss = step(p, o, t)
            losses.append(float(loss))
            if i == 0:
                first = flat(jax.tree_util.tree_map(
                    lambda new, old: (old - new) / 0.01, p, params))
        assert losses[2] < losses[1] < losses[0]
        np.testing.assert_allclose(losses[0], want_loss, rtol=5e-3)
        want = flat(want)
        norm = lambda a: float(np.sqrt(np.sum(np.square(a))))
        floor = float(np.median([norm(w) for w in want.values()]))
        for path, w in want.items():
            assert abs(norm(first[path]) - norm(w)) \
                <= 0.15 * max(norm(w), floor), path
    finally:
        hvd.shutdown()
