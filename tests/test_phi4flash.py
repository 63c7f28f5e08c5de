"""Phi-4-mini-flash-reasoning's layers
(``horovod_tpu/models/phi4flash.py``) against the plain float32
reference the chip benchmark keeps for them
(``benchmarks/chip/families/phi4flash_lm.py``), at a small size on the
CPU with seeded weights and the six-layer pattern the cell runs
(published layers 0, 1, 16, 17, 18, 19): the program in float32 must
agree to rounding, part by part, as a whole and over three steps; the
readers of the shared key-value pair and of the scan's memory all add
into them; and the tied embedding's gradient is the sum of both its
uses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from .chip_bench import _paths  # noqa: F401  (makes chipbench importable)
from .compiled import beside, momentum_step
from chipbench import check, harness, weights

from horovod_tpu.models import phi4flash, train_steps
from horovod_tpu.parallel import flash_attention as fa

pytestmark = [pytest.mark.fast, pytest.mark.interpreter_of_its_own]

FAMILY = harness.load_module("families", "phi4flash_lm")
CONFIG = {
    "vocab_size": 192, "num_hidden_layers": 6, "hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 48, "sliding_window": 8, "layer_norm_eps": 1e-5,
    "published": {"num_hidden_layers": 32},
    "kept_layers": [0, 1, 16, 17, 18, 19],
    "assumed": {"sequence_length": 32,
                "mamba": {"d_inner": 64, "d_state": 2, "d_conv": 4,
                          "dt_rank": 3, "dt_bias_init": -4.6}}}
SZ = FAMILY.sizes(CONFIG, 2)
REF = FAMILY.reference_fns(SZ)
TOL = dict(rtol=3e-5, atol=3e-6)


@pytest.fixture(scope="module")
def model():
    return phi4flash.Phi4FlashLM(dataclasses.replace(
        FAMILY.build_model(SZ).cfg, dtype=jnp.float32))


@pytest.fixture(scope="module")
def params():
    shapes, fans = FAMILY.param_shapes(SZ)
    return weights.make_tree(shapes, fans, seed=13, stream=0)["params"]


@pytest.fixture(scope="module")
def reference():
    """The reference's chain, its stages compiled once for the file."""
    return check.StagedGradient(FAMILY.reference_stages(SZ))


@pytest.fixture(scope="module")
def programs(model, params):
    """The file's two whole-model programs, lowered at its start and
    compiled beside one another and beside the tests of the blocks
    (``tests/compiled.py``): the program's loss and gradients, and the
    gradients of the loss with the embedding untied by hand."""
    table = params["embed"]["embedding"]
    return beside(
        loss_and_grads=jax.jit(jax.value_and_grad(
            train_steps.phi4flash_loss_fn(model))).lower(params, tokens()),
        untied=jax.jit(jax.grad(untied_loss(model, params),
                                argnums=(0, 1))).lower(table, table))


@pytest.fixture(scope="module")
def loss_and_grads(programs):
    return programs["loss_and_grads"]


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.key(3), (2, SZ["seq"], SZ["d"]))


def tokens():
    return FAMILY.make_batch(SZ, 2)(jax.random.key(5))[0]


def flat(tree):
    return {k: v[0] for k, v in weights.flat_shapes(
        jax.tree_util.tree_map(lambda a: (np.asarray(a),), tree)).items()}


def test_the_family_names_the_programs_own_parameters(model):
    program = harness.Program.__new__(harness.Program)
    program.family, program.sz, program.model = FAMILY, SZ, model
    program.shapes, _ = FAMILY.param_shapes(SZ)
    program._check_shapes()


def test_each_kept_layer_is_the_kind_its_published_index_says():
    kinds = [phi4flash.layer_kind(i, 32) for i in range(32)]
    assert kinds[:16:2] == ["mamba"] * 8 and kinds[1:16:2] == ["window"] * 8
    assert kinds[16:18] == ["mamba", "full"]
    assert kinds[18::2] == ["gmu"] * 7 and kinds[19::2] == ["cross"] * 7
    assert [FAMILY.layer_kind(i, 32) for i in range(32)] == kinds
    assert FAMILY.kinds(SZ) == ["mamba", "window", "mamba", "full", "gmu",
                                "cross"]
    for i in (1, 17, 19):
        assert phi4flash.lambda_init(i) == FAMILY.lambda_init(i)
    assert abs(phi4flash.lambda_init(0) - 0.2) < 1e-12


@jax.jit
def carried(params, x):
    """The reference's memory and key-value pair for ``x`` entering
    layers 16 and 17."""
    x, memory, _, _ = REF["block"](params["layer_16"], 16, x, None, None,
                                   None)
    return REF["block"](params["layer_17"], 17, x, memory, None, None)


@pytest.mark.parametrize("index", [1, 16, 17, 18, 19])
def test_a_block_of_the_program_is_the_references(index, model, params, x):
    x17, memory, k, v = carried(params, x)
    ins = {1: (x, None, None, None),
           16: (x, None, None, None), 17: (x, memory, None, None),
           18: (x17, memory, k, v), 19: (x17, memory, k, v)}[index]
    p = params[f"layer_{index}"]
    got = jax.jit(phi4flash.Block(model.cfg, index).apply)(
        {"params": p}, *ins)
    want = jax.jit(REF["block"], static_argnums=1)(p, index, *ins)
    for g, w in zip(got, want):
        assert (g is None) == (w is None)
        if w is not None:
            np.testing.assert_allclose(g, w, **TOL)
    # what a layer does not make it hands on as it got it
    made = {16: (1,), 17: (2, 3)}.get(index, ())
    for j in (1, 2, 3):
        if j not in made and ins[j] is not None:
            assert got[j] is ins[j] or np.array_equal(got[j], ins[j])


def test_the_window_is_on_the_layers_that_have_one_and_on_no_other(
        model, params, x):
    """Layer 1 must not see key 0 from position 8 on; layer 17 must."""
    for index, sees in ((1, False), (17, True)):
        p = params[f"layer_{index}"]
        run = jax.jit(lambda x: phi4flash.Block(model.cfg, index).apply(
            {"params": p}, x, None, None, None)[0])
        moved = x.at[:, 0].set(-x[:, 1])
        far = np.abs(np.asarray(run(moved) - run(x)))[:, SZ["window"]:]
        assert (far.max() > 1e-4) == sees, (index, far.max())


def test_the_programs_attention_runs_through_the_flash_kernels(model,
                                                               params, x):
    """The same block with the kernels in interpret mode in place of the
    dense formulation the CPU takes by default: a window, grouped
    heads and a value head of 2 x 8 against a key head of 8."""
    calls = []

    def through_kernels(q, k, v, window=None):
        calls.append((q.shape, k.shape, v.shape, window))
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=16, block_k=16, interpret=True)

    cfg = dataclasses.replace(model.cfg, attention_fn=through_kernels)
    for index, window in ((1, SZ["window"]), (17, None)):
        p = params[f"layer_{index}"]
        got = jax.jit(phi4flash.Block(cfg, index).apply)(
            {"params": p}, x, None, None, None)[0]
        want = jax.jit(REF["block"], static_argnums=1)(
            p, index, x, None, None, None)[0]
        np.testing.assert_allclose(got, want, **TOL)
        assert calls[-2:] == [((2, 32, 2, 8), (2, 32, 1, 8), (2, 32, 1, 16),
                               window)] * 2


def test_the_whole_loss_and_its_gradients_are_the_references(
        loss_and_grads, params, reference):
    """Through the reference's chain of five: the loss's use of the
    embedding's rows reaches the embedding's own gradient, and the
    memory and the key-value pair collect from every reader."""
    t = tokens()
    loss, grads = loss_and_grads(params, t)
    # as `check.py` and the three steps below call it: one set of
    # stages for the file (on the CPU the precision changes no product)
    with jax.default_matmul_precision("highest"):
        want_loss, _, want = reference(params, {}, (t,))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
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
        loss, g = loss_and_grads(p, t)
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


def untied_loss(model, params):
    """The loss with the lookup reading one copy of the table and the
    head another."""
    t = tokens()

    def untied(lookup, head):
        p = {**params, "embed": {"embedding": lookup}}
        hidden = model.apply({"params": p}, t)
        from horovod_tpu.models.transformer import lm_loss_from_hidden
        return lm_loss_from_hidden(hidden, head.T, t)

    return untied


def test_the_tied_embeddings_gradient_is_the_sum_of_both_uses(
        params, programs, loss_and_grads):
    """Untie by hand: the lookup reads one copy of the table and the
    head another; the tied gradient is the two copies' sum."""
    table = params["embed"]["embedding"]
    g_lookup, g_head = programs["untied"](table, table)
    tied = loss_and_grads(params, tokens())[1]["embed"]["embedding"]
    assert float(jnp.abs(g_lookup).max()) > 0 < float(jnp.abs(g_head).max())
    np.testing.assert_allclose(tied, g_lookup + g_head, rtol=1e-4, atol=3e-6)


def test_every_reader_adds_into_the_shared_pair_and_the_memory(model,
                                                               params, x):
    """A model that keeps two gated memory units and two cross layers
    (published 18 to 21): the gradient reaching layer 16's memory is
    the sum of what each unit sends, the gradient reaching layer 17's
    k and v the sum of what each cross layer sends (and nothing else
    reads them)."""
    x17, memory, k, v = carried(params, x)
    cfg = model.cfg
    twice = {18: params["layer_18"], 19: params["layer_19"],
             20: params["layer_18"], 21: params["layer_19"]}

    def tail(memory, k, v, readers):
        """The sum of the outputs of the layers in ``readers``, each
        reading the same residual."""
        total = 0.0
        for i in readers:
            out = phi4flash.Block(cfg, i).apply(
                {"params": twice[i]}, x17, memory, k, v)[0]
            total = total + jnp.sum(out * jnp.cos(out + i))
        return total

    def chain(memory, k, v):
        """The four layers one after another, as the model runs them."""
        h = x17
        for i in (18, 19, 20, 21):
            h, memory, k, v = phi4flash.Block(cfg, i).apply(
                {"params": twice[i]}, h, memory, k, v)
        return jnp.sum(jnp.sin(h))

    def sent_by(readers):
        """What ``readers`` send back to (memory, k, v)."""
        return jax.jit(jax.grad(
            lambda m_, k_, v_: tail(m_, k_, v_, readers),
            argnums=(0, 1, 2)))(memory, k, v)

    both = sent_by((18, 19, 20, 21))
    parts = [sent_by((i,)) for i in (18, 19, 20, 21)]
    for j, name in enumerate(("memory", "k", "v")):
        np.testing.assert_allclose(
            both[j], sum(p[j] for p in parts), rtol=1e-5, atol=1e-7,
            err_msg=name)
    # the units send nothing to k and v, the cross layers nothing to M
    assert all(float(jnp.abs(parts[i][j]).max()) == 0.0
               for i, j in ((0, 1), (0, 2), (2, 1), (2, 2), (1, 0), (3, 0)))
    assert all(float(jnp.abs(parts[i][0]).max()) > 0 for i in (0, 2))
    assert all(float(jnp.abs(parts[i][j]).max()) > 0
               for i in (1, 3) for j in (1, 2))
    # and through the model's own chain all three arrive
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.jit(jax.grad(
        chain, argnums=(0, 1, 2)))(memory, k, v))
