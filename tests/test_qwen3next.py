"""Qwen3-Next's layers (``horovod_tpu/models/qwen3next.py``) against the
plain float32 reference the chip benchmark keeps for them
(``benchmarks/chip/families/qwen3next_lm.py``), at a small size on the
CPU with seeded weights and the four-layer pattern the cell runs
(published layers 0 to 3: three Gated DeltaNet layers and one of gated
attention, an expert layer in each): the program in float32 must agree
to rounding, part by part, as a whole and over three steps; the sixteen
shares of the experts add up to the uncut layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from .chip_bench import _paths  # noqa: F401  (makes chipbench importable)
from .compiled import beside, momentum_step, weights_under
from chipbench import check, harness, weights

from horovod_tpu.models import glm_moe, qwen3next, train_steps
from horovod_tpu.parallel import flash_attention as fa

pytestmark = [pytest.mark.fast, pytest.mark.time_limit(170),
              pytest.mark.interpreter_of_its_own]

FAMILY = harness.load_module("families", "qwen3next_lm")
CONFIG = {
    "vocab_size": 192, "num_hidden_layers": 4, "hidden_size": 32,
    "full_attention_interval": 4, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 24,
    "num_experts": 2, "num_experts_per_tok": 4, "rms_norm_eps": 1e-6,
    "published": {"num_hidden_layers": 48},
    "kept_layers": [0, 1, 2, 3],
    "deployment": {"router_width": 32, "expert_offset": 6},
    "assumed": {"sequence_length": 40,
                "gates": {"a_log_init": 2.08, "dt_bias_init": -4.6}}}
SZ = FAMILY.sizes(CONFIG, 2)
REF = FAMILY.reference_fns(SZ)
TOL = dict(rtol=3e-5, atol=3e-6)


@pytest.fixture(scope="module")
def model():
    return qwen3next.Qwen3NextLM(dataclasses.replace(
        FAMILY.build_model(SZ).cfg, dtype=jnp.float32))


@pytest.fixture(scope="module")
def params():
    """Seeded weights on which no token's choice of experts sits on a
    near tie: a rounding that flips one (seed 13 has such a token in
    layer 1) is another function of the weights, and the gradients
    below it then agree to 3e-4 of their largest entry, not to 2e-5."""
    shapes, fans = FAMILY.param_shapes(SZ)
    return weights.make_tree(shapes, fans, seed=15, stream=0)["params"]


@pytest.fixture(scope="module")
def reference():
    """The reference's chain, its stages compiled once for the file."""
    return check.StagedGradient(FAMILY.reference_stages(SZ))


@pytest.fixture(scope="module")
def programs(model, params):
    """The program's loss, counts and gradients, lowered at the file's
    start and compiled beside the tests of its parts
    (``tests/compiled.py``: 11 s of XLA)."""
    return beside(loss_and_grads=jax.jit(jax.value_and_grad(
        train_steps.qwen3next_loss_fn(model), has_aux=True)).lower(
            params, tokens()))


@pytest.fixture(scope="module")
def loss_and_grads(programs):
    return programs["loss_and_grads"]


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.key(3), (2, SZ["seq"], SZ["d"]))


def tokens():
    return FAMILY.make_batch(SZ, 2)(jax.random.key(5))[0]


def positions(x):
    return jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])


def flat(tree):
    return {k: v[0] for k, v in weights.flat_shapes(
        jax.tree_util.tree_map(lambda a: (np.asarray(a),), tree)).items()}


def test_the_family_names_the_programs_own_parameters(model):
    program = harness.Program.__new__(harness.Program)
    program.family, program.sz, program.model = FAMILY, SZ, model
    program.shapes, _ = FAMILY.param_shapes(SZ)
    program._check_shapes()


def test_each_kept_layer_is_the_kind_its_published_index_says():
    kinds = [qwen3next.layer_kind(i, 4) for i in range(48)]
    assert kinds.count("attention") == 12 and kinds.count("delta") == 36
    assert all(k == ("attention" if i % 4 == 3 else "delta")
               for i, k in enumerate(kinds))
    assert [FAMILY.layer_kind(i, 4) for i in range(48)] == kinds
    assert FAMILY.kinds(SZ) == ["delta", "delta", "delta", "attention"]


def part(name, model, params, x):
    cfg = model.cfg
    if name == "delta_net":
        p = params["layer_1"]["mixer"]
        return (jax.jit(qwen3next.GatedDeltaNet(cfg).apply)({"params": p}, x),
                jax.jit(REF["delta_net"])(p, x))
    if name == "attention":
        p = params["layer_3"]["mixer"]
        return (jax.jit(qwen3next.GatedAttention(cfg).apply)(
            {"params": p}, x, positions(x)), jax.jit(REF["attention"])(p, x))
    if name == "expert_layer":
        p = params["layer_2"]["moe"]
        return (jax.jit(glm_moe.ExpertLayer(cfg).apply)({"params": p}, x)[0],
                jax.jit(REF["expert_layer"])(p, x))
    index = int(name[-1])
    p = params[f"layer_{index}"]
    return (jax.jit(qwen3next.Block(cfg, index).apply)(
        {"params": p}, x, positions(x))[0],
        jax.jit(REF["block"], static_argnums=1)(p, index, x))


@pytest.mark.parametrize("name", ["delta_net", "attention", "expert_layer",
                                  "block_0", "block_3"])
def test_a_part_of_the_program_is_the_references(name, model, params, x):
    got, want = part(name, model, params, x)
    np.testing.assert_allclose(got, want, **TOL)


def test_the_rotary_turns_a_quarter_of_the_head_and_no_more(model, params,
                                                            x):
    """Two sequences that differ by a shift: without a positional
    signal a causal layer's outputs would shift with them. The rotary
    part breaks that; a rotary that reached no dimension would not."""
    assert model.cfg.rotary_dim == 4 == SZ["rotary"]
    p = params["layer_3"]["mixer"]
    run = jax.jit(lambda x, pos: qwen3next.GatedAttention(model.cfg).apply(
        {"params": p}, x, pos))
    here = run(x, positions(x))
    np.testing.assert_allclose(run(x, positions(x)), here)
    moved = run(x, positions(x) * 3)
    assert float(jnp.abs(moved - here)[:, 1:].max()) > 1e-4
    # position 0 attends to itself alone: no angle between q and k
    np.testing.assert_allclose(moved[:, 0], here[:, 0], **TOL)


def test_gated_attention_runs_through_the_flash_kernels_at_a_head_of_256(
        monkeypatch):
    """The cell's head: 256 wide, rotary on its first 64, eight query
    heads a key-value head; the kernels in interpret mode against the
    reference's dense softmax."""
    config = dict(CONFIG, head_dim=256, num_attention_heads=8,
                  num_key_value_heads=1,
                  assumed=dict(CONFIG["assumed"], sequence_length=32))
    sz = FAMILY.sizes(config, 1)
    shapes, fans = FAMILY.param_shapes(sz)
    p = weights_under(shapes, fans, 7, 0, "params/layer_3/mixer")
    calls = []

    def through_kernels(q, k, v):
        calls.append((q.shape, k.shape, v.shape))
        return fa.flash_attention(q, k, v, causal=True, block_q=16,
                                  block_k=16, interpret=True)

    monkeypatch.setattr(qwen3next, "best_grouped_attention", through_kernels)
    cfg = dataclasses.replace(FAMILY.build_model(sz).cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(8), (1, 32, sz["d"]))
    got = jax.jit(qwen3next.GatedAttention(cfg).apply)(
        {"params": p}, x, positions(x))
    assert cfg.rotary_dim == 64
    assert calls == [((1, 32, 8, 256), (1, 32, 1, 256), (1, 32, 1, 256))]
    np.testing.assert_allclose(
        got, jax.jit(FAMILY.reference_fns(sz)["attention"])(p, x),
        rtol=1e-4, atol=1e-5)


def test_the_whole_loss_and_its_gradients_are_the_references(
        loss_and_grads, params, reference):
    t = tokens()
    (loss, counts), grads = loss_and_grads(params, t)
    # as `check.py` and the three steps below call it: one set of
    # stages for the file (on the CPU the precision changes no product)
    with jax.default_matmul_precision("highest"):
        want_loss, _, want = reference(params, {}, (t,))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    assert counts.shape == (4, SZ["experts_held"] + 2)
    assert (np.asarray(counts).sum(axis=1) == t.size * SZ["top_k"]).all()
    got, want = flat(grads), flat(want)
    assert set(got) == set(want)
    for path in want:
        np.testing.assert_allclose(
            got[path], want[path], rtol=3e-4,
            atol=3e-6 * float(np.abs(want[path]).max() + 1), err_msg=path)


def test_three_steps_follow_the_references(loss_and_grads, params,
                                           reference):
    """SGD with momentum, three steps on one batch: the program's
    losses and its parameters' change against the reference's."""
    t = tokens()
    tx = optax.sgd(0.01, momentum=0.9)

    @jax.jit
    def apply(p, o, g):
        updates, o = tx.update(g, o, p)
        return optax.apply_updates(p, updates), o

    def step(p, o):
        (loss, _), g = loss_and_grads(p, t)
        return (*apply(p, o, g), loss)

    p, o, losses = params, tx.init(params), []
    want_p, want_losses = params, []
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    for _ in range(3):
        p, o, loss = step(p, o)
        losses.append(float(loss))
        with jax.default_matmul_precision("highest"):
            want_loss, _, g = reference(want_p, {}, (t,))
        want_p, trace = momentum_step(want_p, trace, g)
        want_losses.append(float(want_loss))
    np.testing.assert_allclose(losses, want_losses, rtol=2e-6)
    start = {"params": params, "aux": {}}
    np.testing.assert_allclose(
        check.diff_norms({"params": p, "aux": {}}, start),
        check.diff_norms({"params": want_p, "aux": {}}, start), rtol=2e-3)


def test_the_sixteen_shares_add_up_to_the_uncut_layer(model, x):
    """The share tied to the model: 32 experts as sixteen chips' two
    each. Every chip's routed part, plus the gated shared expert
    counted once, is the reference's layer with all 32 experts."""
    e, held = SZ["experts"], SZ["experts_held"]
    whole = dict(SZ, experts_held=e, expert_offset=0)
    shapes, fans = FAMILY.param_shapes(whole)
    p = weights_under(shapes, fans, 13, 0, "params/layer_0/moe")
    want = jax.jit(FAMILY.reference_fns(whole)["expert_layer"])(p, x)
    shared = jax.jit(lambda p, x: jax.nn.sigmoid(
        x @ p["shared_gate"]["kernel"]) * FAMILY._swiglu(p["shared"], x))(
            p, x)

    @jax.jit
    def shares(p, x):
        """Every chip's ``(y, counts)``, one program for the sixteen."""
        out = []
        for offset in range(0, e, held):
            mine = dict(p, experts={k: v[offset:offset + held]
                                    for k, v in p["experts"].items()})
            cfg = dataclasses.replace(model.cfg, expert_offset=offset)
            out.append(glm_moe.ExpertLayer(cfg).apply({"params": mine}, x))
        return out

    total, seen = shared, 0
    for y, counts in shares(p, x):
        total = total + (y - shared)
        seen += int(counts[:held].sum())
        assert counts[glm_moe.DROPPED] == 0
    assert e // held == 16 and seen == x.shape[0] * x.shape[1] * SZ["top_k"]
    np.testing.assert_allclose(total, want, **TOL)
