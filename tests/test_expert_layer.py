"""The one expert layer of every sparse model
(``horovod_tpu.models.glm_moe.ExpertLayer``) under both scorings, with
and without a shared expert, against a dense masked sum written here,
the constant in its normaliser, its row buffer's tiers, the tier that
walks the rows a slab at a time against the one-buffer result with
every assignment forced onto held experts, the buffer's index plan and
the way back to token order against the pick of every assignment they
replaced, what the differentiated layer traces (no array of tokens x k
rows by width, no scatter of tokens x k scalars) and says of it in the
registry, the eight shares of a layer adding up to the uncut layer, the
choice limited to groups against a literal loop over tokens (a group
whose two best lose is never chosen from; one group is the choice as it
was, bit for bit), the router (the choice by rounds of max against
``jax.lax.top_k`` under ties, the chosen scores and their gradients
against the gather's, the product at full precision whatever the rows'
type) and what it says of itself in the registry, and the two older
models' parameter trees as they were. (PR 40's tests: 3 s cold; PR
41's grouped cases: 4 s; PR 47's router cases: 4 s.)"""

import dataclasses
import functools
import hashlib
import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import glm_moe, lfm2, qwen3next

pytestmark = [pytest.mark.fast, pytest.mark.time_limit(120),
              pytest.mark.interpreter_of_its_own]

D, WIDTH, EXPERTS, HELD, OFFSET, K = 32, 16, 16, 4, 8, 4
TOL = dict(rtol=3e-5, atol=3e-6)


def config(scoring, **over):
    """A configuration of one of the three models at one small size:
    ``no_shared`` is the one without a shared expert (sigmoid scores,
    1e-6 in the normaliser)."""
    if scoring == "no_shared":
        return dataclasses.replace(lfm2.Lfm2MoeConfig(
            hidden_size=D, moe_intermediate_size=WIDTH,
            n_routed_experts=EXPERTS, num_experts_per_tok=K,
            experts_held=HELD, expert_offset=OFFSET, dtype=jnp.float32),
            **over)
    if scoring == "sigmoid":
        return dataclasses.replace(glm_moe.GlmMoeConfig(
            hidden_size=D, moe_intermediate_size=WIDTH,
            n_routed_experts=EXPERTS, num_experts_per_tok=K,
            experts_held=HELD, expert_offset=OFFSET, dtype=jnp.float32),
            **over)
    return dataclasses.replace(qwen3next.Qwen3NextConfig(
        hidden_size=D, moe_intermediate_size=WIDTH,
        shared_intermediate_size=24, n_routed_experts=EXPERTS,
        num_experts_per_tok=K, experts_held=HELD, expert_offset=OFFSET,
        dtype=jnp.float32), **over)


def dense_masked_sum(cfg, p, x):
    """Every held expert over every token, weighted by the router's
    weight for it (zero where the token did not choose it: the chosen
    scores over their sum plus the configuration's constant), plus the
    shared expert where there is one, gated where the configuration
    says so."""
    xf = x.reshape(-1, D)
    logits = xf @ p["router"]["kernel"]
    if cfg.scoring == "softmax":
        scores = jax.nn.softmax(logits, -1)
        choice = scores
    else:
        scores = jax.nn.sigmoid(logits)
        choice = scores + p["router"]["bias"]
    _, chosen = jax.lax.top_k(choice, K)
    picked = scores * jnp.sum(
        jax.nn.one_hot(chosen, cfg.n_routed_experts), axis=1)
    weights = cfg.routed_scaling_factor * picked \
        / (jnp.sum(picked, -1, keepdims=True) + cfg.topk_weight_eps)
    swiglu = lambda g, u, d_: (nn.silu(xf @ g) * (xf @ u)) @ d_
    y = jnp.zeros_like(xf)
    if cfg.shared_intermediate_size:
        shared = p["shared"]
        y = swiglu(shared["gate"]["kernel"], shared["up"]["kernel"],
                   shared["down"]["kernel"])
    if cfg.shared_expert_gate:
        y = y * jax.nn.sigmoid(xf @ p["shared_gate"]["kernel"])
    for j in range(cfg.experts_held):
        y = y + weights[:, cfg.expert_offset + j, None] * swiglu(
            p["experts"]["gate"][j], p["experts"]["up"][j],
            p["experts"]["down"][j])
    return y.reshape(x.shape)


@functools.cache
def _initialised(cfg):
    """A configuration's leaves as initialised, made once for the
    cases that share it (``init`` traces the whole layer)."""
    x = jax.random.normal(jax.random.key(1), (2, 24, D))
    return jax.jit(glm_moe.ExpertLayer(cfg).init)(
        jax.random.key(2), x)["params"], x


def layer_and_params(cfg):
    """The layer, a tree of its own of the leaves as initialised (a
    case may put leaves of its own into it), and the rows."""
    p, x = _initialised(cfg)
    return glm_moe.ExpertLayer(cfg), jax.tree_util.tree_map(
        lambda leaf: leaf, p), x


def dense(cfg, p, x):
    """``dense_masked_sum`` as one program."""
    return jax.jit(lambda p, x: dense_masked_sum(cfg, p, x))(p, x)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax", "no_shared"])
def test_the_layer_is_the_dense_masked_sum_under_either_scoring(scoring):
    """``no_shared``: a configuration whose ``shared_intermediate_size``
    is 0 has no ``shared`` leaves, and its layer is the routed sum
    alone."""
    cfg = config(scoring)
    layer, p, x = layer_and_params(cfg)
    assert ("bias" in p["router"]) == (scoring != "softmax")
    assert ("shared_gate" in p) == (scoring == "softmax")
    if scoring == "no_shared":
        assert set(p) == {"router", "experts"}
    else:
        assert p["shared"]["up"]["kernel"].shape[1] \
            == (WIDTH if scoring == "sigmoid" else 24)
    if scoring != "softmax":
        p["router"]["bias"] = jax.random.normal(jax.random.key(3),
                                                (EXPERTS,)) * 0.3
    weight = jax.random.normal(jax.random.key(4), x.shape)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(fn(p, x) * weight), argnums=(0, 1),
            has_aux=False))(p, x)

    got, got_grads = both(lambda p, x: layer.apply({"params": p}, x)[0])
    want, want_grads = both(lambda p, x: dense_masked_sum(cfg, p, x))
    np.testing.assert_allclose(got, want, atol=1e-4)   # a sum that cancels
    for g, w in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max() + 1))
    y, counts = jax.jit(layer.apply)({"params": p}, x)
    np.testing.assert_allclose(y, dense(cfg, p, x), **TOL)
    assert int(counts.sum()) == 48 * K and int(counts[glm_moe.DROPPED]) == 0


@pytest.mark.parametrize("forced", [False, True],
                         ids=["the_sound_tier", "the_walked_tier"])
def test_a_buffer_an_eighth_of_the_assignments_finds_its_tokens(forced):
    """Two of 32 experts held: the buffer is 24 rows, an eighth of the
    192 assignments, and the way back to token order picks those 24 and
    one row a token; with every token forced onto the held pair the
    walked tier does the same a slab at a time."""
    cfg = config("softmax", n_routed_experts=32, experts_held=2,
                 num_experts_per_tok=K)
    layer, p, x = layer_and_params(cfg)
    cap = round(glm_moe.row_tiers(2, 32)[0] * 48 * K)
    assert 8 * cap == 48 * K
    if forced:
        x = jnp.abs(x) + 0.1
        kernel = np.asarray(p["router"]["kernel"]) * 0.1
        kernel[:, OFFSET:OFFSET + 2] += 1.0
        p["router"] = {"kernel": jnp.asarray(kernel)}
    weight = jax.random.normal(jax.random.key(4), x.shape)

    def both(fn):
        return jax.jit(jax.grad(
            lambda p, x: jnp.sum(fn(p, x) * weight), argnums=(0, 1)))(p, x)

    got = both(lambda p, x: layer.apply({"params": p}, x)[0])
    want = both(lambda p, x: dense_masked_sum(cfg, p, x))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max() + 1))
    y, counts = jax.jit(layer.apply)({"params": p}, x)
    np.testing.assert_allclose(y, dense(cfg, p, x), **TOL)
    assert int(counts[glm_moe.DROPPED]) == 0
    assert int(counts[:2].sum()) == (48 * 2 if forced else counts[:2].sum())
    assert (int(counts[:2].sum()) > cap) == forced


@functools.cache
def _routed_choices(scoring):
    """[48, K] choices of the layer's own router under ``scoring`` with
    six of sixteen experts held, then bent: token 5 chooses four held
    experts and nothing else, and nobody chooses the last held one."""
    cfg = config(scoring, experts_held=6)
    layer, p, x = layer_and_params(cfg)
    _, sown = jax.jit(lambda p, x: layer.apply(
        {"params": p}, x, mutable=["intermediates"]))(p, x)
    chosen = np.array(sown["intermediates"]["chosen"][0])
    last = OFFSET + 5
    for row in chosen:
        spare = iter(sorted(set(range(OFFSET)) - set(row)))
        row[row == last] = next(spare)
    chosen[5] = OFFSET + np.arange(K)
    return cfg, chosen


def routed_choices(scoring):
    cfg, chosen = _routed_choices(scoring)
    return cfg, chosen.copy()


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_plan_is_the_stable_sorts_on_the_held_rows(scoring):
    """``expert_order`` and ``span_of`` against NumPy's stable argsort
    and its inverse, on the first tier's buffer and on a slab of 32
    rows from row 16 on: the rows' assignments, the sizes, the live
    rows in token order, each token's run."""
    cfg, chosen = routed_choices(scoring)
    n, held_n = chosen.shape[0], cfg.experts_held
    local = chosen - OFFSET
    held = (local >= 0) & (local < held_n)
    group = np.where(held, local, held_n).reshape(-1)
    order = np.argsort(group, kind="stable")
    total = int(held.sum())
    got_order, sizes = jax.jit(glm_moe.expert_order, static_argnums=1)(
        jnp.asarray(group, jnp.int32), held_n)
    np.testing.assert_array_equal(got_order, order)
    np.testing.assert_array_equal(sizes,
                                  np.bincount(group, minlength=held_n + 1))
    assert sizes[held_n - 1] == 0 and held[5].all() and total >= 48
    for lo, cap in ((0, round(glm_moe.row_tiers(held_n, EXPERTS)[0]
                              * n * K)), (16, 32)):
        rows = np.arange(lo, lo + cap)
        live = rows < total
        span = jax.jit(glm_moe.span_of, static_argnums=(2, 3))(
            jnp.asarray(order[lo:lo + cap], jnp.int32), jnp.asarray(live),
            n, K)
        inside = int(live.sum())
        # the buffer's assignments ascending are its tokens ascending
        by_token = np.argsort(order[lo:lo + cap][live], kind="stable")
        np.testing.assert_array_equal(span.row[:inside], by_token)
        tokens = order[lo:lo + cap][live][by_token] // K
        np.testing.assert_array_equal(span.token[:inside], tokens)
        assert (np.asarray(span.token[inside:cap]) == n).all()
        assert (np.asarray(span.token[cap:]) == -1).all() \
            and span.token.shape[0] % glm_moe.RUN_BLOCK == 0
        has = np.isin(np.arange(n), tokens)
        np.testing.assert_array_equal(
            span.first,
            np.where(has, np.searchsorted(tokens, np.arange(n)), cap))
    assert not has[:5].all() and inside == cap    # a slab inside


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_way_back_is_the_pick_of_every_assignment(scoring):
    """``rows_from_experts`` and its transpose against what they
    replace (every assignment picks its row, the absent are masked, a
    token's K are summed in float32), bfloat16 rows, bit for bit; a
    cotangent's rows behind the last live one hold NaN and meet
    nothing."""
    cfg, chosen = routed_choices(scoring)
    n, held_n = chosen.shape[0], cfg.experts_held
    local = chosen - OFFSET
    held = (local >= 0) & (local < held_n)
    group = np.where(held, local, held_n).reshape(-1)
    order = np.argsort(group, kind="stable")
    inv = np.argsort(order)
    cap, total = 160, int(held.sum())     # two blocks of the run sums
    live = np.arange(cap) < total
    rows = jnp.where(live[:, None], jax.random.normal(
        jax.random.key(7), (cap, D), jnp.bfloat16), 0)
    grad = jax.random.normal(jax.random.key(8), (n, D), jnp.bfloat16)

    @jax.jit
    def both(rows, grad):
        span = glm_moe.span_of(jnp.asarray(order[:cap], jnp.int32),
                               jnp.asarray(live), n, K)
        back, pull = jax.vjp(
            lambda r: glm_moe.rows_from_experts(r, span, K), rows)
        there, push = jax.vjp(
            lambda x: glm_moe.rows_to_experts(x, span, K), grad)
        return (back, pull(grad)[0], there,
                push(jnp.where(live[:, None], rows, jnp.nan))[0])

    back, pulled, there, pushed = both(rows, grad)
    picked = np.where(held.reshape(-1, 1), np.asarray(
        rows.astype(jnp.float32))[np.minimum(inv, cap - 1)], 0)
    want = jnp.asarray(picked.reshape(n, K, D).sum(1)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(back, want)
    np.testing.assert_array_equal(pushed, want)
    np.testing.assert_array_equal(there, grad[order[:cap] // K])
    np.testing.assert_array_equal(
        pulled, jnp.where(live[:, None], grad[order[:cap] // K], 0))
    assert cap > glm_moe.RUN_BLOCK and cap % glm_moe.RUN_BLOCK


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax", "no_shared"])
def test_the_differentiated_layer_handles_no_array_of_every_assignment(
        scoring):
    """The traced value and gradients of the layer, both tiers: no
    array of tokens x k rows by the width, no scatter of tokens x k
    updates, and the one sort of tokens x k keys is the plan's."""
    cfg = config(scoring)
    layer, p, x = layer_and_params(cfg)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda p, x: jnp.sum(layer.apply({"params": p}, x)[0]),
        argnums=(0, 1)))(p, x)
    every = 48 * K
    assert round(glm_moe.row_tiers(HELD, EXPERTS)[0] * every) < every
    sorts = 0
    for eqn in _equations(jaxpr.jaxpr):
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert not (len(shape) == 2 and shape[0] >= every
                        and shape[1] == D), (eqn.primitive, shape)
        if eqn.primitive.name.startswith("scatter"):
            assert eqn.invars[2].aval.shape[0] < every, eqn
        if eqn.primitive.name == "sort" \
                and eqn.invars[0].aval.shape[0] >= every:
            sorts += 1
    assert sorts == 1


def test_a_differentiated_layer_writes_its_rows_into_the_registry(
        monkeypatch):
    import horovod_tpu.jax as hvd
    from horovod_tpu import metrics
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    hvd.init()
    try:
        layer, p, x = layer_and_params(config("sigmoid"))
        loss = lambda p, x: jnp.sum(layer.apply({"params": p}, x)[0])
        loss(p, x)
        assert not [name for name in metrics()["local"]
                    if name.startswith("hvd_moe_rows_moved")]
        jax.grad(loss)(p, x)
        local = metrics()["local"]
        # the buffer's 96 rows filled up to a block of 128 places
        for kind, want in (("assignments", 48 * K), ("buffer_rows", 96),
                           ("picked_back", 128 + 48)):
            assert local[f'hvd_moe_rows_moved{{kind="{kind}"}}']["v"] \
                == want
    finally:
        hvd.shutdown()


def test_a_traced_layer_writes_its_router_into_the_registry(monkeypatch):
    """``hvd_moe_route`` holds the layer traced last: its rounds of max
    (k, and two more where the choice is limited to groups), and no
    sort and no gather."""
    import horovod_tpu.jax as hvd
    from horovod_tpu import metrics
    monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
    hvd.init()
    try:
        _, p, x = layer_and_params(config("sigmoid"))
        for cfg, rounds in (
                (config("sigmoid"), K),
                (config("sigmoid", n_group=4, topk_group=2,
                        num_experts_per_tok=2), 4)):
            jax.eval_shape(glm_moe.ExpertLayer(cfg).apply, {"params": p}, x)
            local = metrics()["local"]
            for kind, want in (("max_rounds", rounds), ("sorts", 0),
                               ("gathers", 0)):
                assert local[f'hvd_moe_route{{kind="{kind}"}}']["v"] == want
    finally:
        hvd.shutdown()


def test_a_scoring_the_layer_does_not_know_is_refused():
    cfg = dataclasses.replace(config("softmax"), scoring="tanh")
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        layer_and_params(cfg)


@pytest.mark.parametrize("held, experts, want", [
    (8, 64, (0.25, 1.0)),        # glm47flash-injit-1chip: the quarter it had
    (32, 512, (0.125, 1.0)),     # qwen3next-injit-1chip: 20,480 rows
    (4, 16, (0.5, 1.0)),
    (32, 64, (1.0,)), (64, 64, (1.0,))])
def test_the_tiers_follow_the_share_held(held, experts, want):
    assert glm_moe.row_tiers(held, experts) == want


def test_the_first_tier_follows_the_configurations_headroom():
    """``row_tier_headroom`` (2 in the three older configurations, 8 in
    the one that holds a sixty-fourth): the buffer that ran is the
    first tier where it holds the held assignments, and the result is
    the dense sum at either size."""
    assert glm_moe.row_tiers(8, 512) == (1 / 32, 1.0)
    assert glm_moe.row_tiers(8, 512, 8.0) == (1 / 8, 1.0)
    assert glm_moe.row_tiers(8, 64, 8.0) == (1.0,)
    from horovod_tpu.models import ling3flash
    assert ling3flash.Ling3FlashConfig().row_tier_headroom == 8.0
    assert config("sigmoid").row_tier_headroom == 2.0 \
        == config("softmax").row_tier_headroom \
        == config("no_shared").row_tier_headroom
    wide = config("sigmoid", experts_held=2, row_tier_headroom=4.0)
    layer, p, x = layer_and_params(wide)
    y, counts = jax.jit(layer.apply)({"params": p}, x)
    np.testing.assert_allclose(y, dense(wide, p, x), **TOL)
    narrow = dataclasses.replace(wide, row_tier_headroom=2.0)
    y2, counts2 = jax.jit(glm_moe.ExpertLayer(narrow).apply)({"params": p}, x)
    np.testing.assert_allclose(y2, y, **TOL)
    np.testing.assert_array_equal(counts, counts2)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_walked_tier_is_the_one_buffer(scoring, monkeypatch):
    """Every token's four choices forced onto the four held experts: 192
    assignments, all held, against a first tier of 96 rows. The walked
    tier takes them in two slabs; one buffer of 192 rows gives the
    same, and the dense sum says both are right."""
    cfg = config(scoring)
    layer, p, x = layer_and_params(cfg)
    kernel = np.asarray(p["router"]["kernel"]) * 0.1
    if scoring == "sigmoid":
        bias = np.zeros(EXPERTS, np.float32)
        bias[OFFSET:OFFSET + HELD] = 10.0
        p["router"] = {"kernel": jnp.asarray(kernel),
                       "bias": jnp.asarray(bias)}
    else:
        # positive inputs and a large column: the held experts' logits
        # lead on every token
        x = jnp.abs(x) + 0.1
        kernel[:, OFFSET:OFFSET + HELD] += 1.0
        p["router"] = {"kernel": jnp.asarray(kernel)}
    weight = jax.random.normal(jax.random.key(6), x.shape)

    def run():
        def loss(p, x):
            y, counts = layer.apply({"params": p}, x)
            return jnp.sum(y * weight), (y, counts)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(p, x)

    (_, (walked, counts)), walked_grads = run()
    monkeypatch.setattr(glm_moe, "row_tiers", lambda *_: (1.0,))
    (_, (whole, counts_one)), whole_grads = run()
    assert int(counts[:HELD].sum()) == 48 * K == int(counts_one[:HELD].sum())
    assert int(counts[glm_moe.ABSENT]) == 0 == int(counts[glm_moe.DROPPED])
    np.testing.assert_allclose(walked, whole, **TOL)
    np.testing.assert_allclose(walked, dense(cfg, p, x), **TOL)
    for g, w in zip(jax.tree_util.tree_leaves(walked_grads),
                    jax.tree_util.tree_leaves(whole_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max() + 1))


def test_a_ragged_last_slab_loses_nothing(monkeypatch):
    """Tiers of 0.3: 192 assignments in slabs of 58, the last one 18
    rows long and padded."""
    cfg = config("sigmoid")
    monkeypatch.setattr(glm_moe, "row_tiers", lambda *_: (0.3, 1.0))
    layer, p, x = layer_and_params(cfg)
    bias = np.zeros(EXPERTS, np.float32)
    bias[[OFFSET, OFFSET + 1, OFFSET + 3, 0]] = 10.0
    p["router"] = dict(p["router"], bias=jnp.asarray(bias))
    y, counts = jax.jit(layer.apply)({"params": p}, x)
    assert int(counts[:HELD].sum()) == 48 * 3 > 58
    assert int(counts[glm_moe.DROPPED]) == 0
    np.testing.assert_allclose(y, dense(cfg, p, x), **TOL)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax", "no_shared"])
def test_the_constant_in_the_normaliser_is_read(scoring):
    """``topk_weight_eps``: a constant large enough to show (the
    published 1e-6 moves a weight by 4e-7 of itself) gives the dense
    sum with that constant, and another result than the bare sum."""
    bare = config(scoring, topk_weight_eps=0.0)
    cfg = config(scoring, topk_weight_eps=0.5)
    layer, p, x = layer_and_params(cfg)
    y, _ = jax.jit(layer.apply)({"params": p}, x)
    np.testing.assert_allclose(y, dense(cfg, p, x), **TOL)
    without, _ = jax.jit(glm_moe.ExpertLayer(bare).apply)({"params": p}, x)
    np.testing.assert_allclose(without, dense(bare, p, x), **TOL)
    assert float(jnp.abs(y - without).max()) > 1e-3
    assert config("no_shared").topk_weight_eps == 1e-6
    assert glm_moe.GlmMoeConfig().topk_weight_eps == 0.0 \
        == qwen3next.Qwen3NextConfig().topk_weight_eps


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share tied to the model: 64 experts as eight chips' eight
    each (offsets 0, 8, ..., 56). With no shared expert there is
    nothing to count once: the chips' outputs sum to the layer that
    holds all 64, and every assignment is held by exactly one chip."""
    experts, held = 64, 8
    whole = config("no_shared", n_routed_experts=experts,
                   experts_held=experts, expert_offset=0)
    layer, p, x = layer_and_params(whole)
    p["router"]["bias"] = jax.random.normal(jax.random.key(3),
                                            (experts,)) * 0.3
    want = dense(whole, p, x)

    @jax.jit
    def shares(p, x):
        """Every chip's ``(y, counts)``, one program for the eight."""
        out = []
        for offset in range(0, experts, held):
            mine = dict(p, experts={k: v[offset:offset + held]
                                    for k, v in p["experts"].items()})
            cfg = dataclasses.replace(whole, experts_held=held,
                                      expert_offset=offset)
            out.append(glm_moe.ExpertLayer(cfg).apply({"params": mine}, x))
        return out

    total, seen = 0.0, 0
    for y, counts in shares(p, x):
        assert counts.shape == (held + 2,) and counts[glm_moe.DROPPED] == 0
        assert int(counts.sum()) == 48 * K
        total = total + y
        seen += int(counts[:held].sum())
    assert seen == 48 * K
    np.testing.assert_allclose(total, want, **TOL)
    np.testing.assert_allclose(
        jax.jit(layer.apply)({"params": p}, x)[0], want, **TOL)


# -- the choice limited to groups --------------------------------------------

GROUPS, TOP_GROUPS = 4, 2


def literal_grouped_choice(biased, n_group, topk_group, k):
    """Token by token on the host: a group's score is the sum of its
    two largest entries, the best ``topk_group`` groups stay, and the
    ``k`` largest entries inside them are the choice."""
    chosen = []
    for row in np.asarray(biased):
        groups = row.reshape(n_group, -1)
        score = np.sort(groups, -1)[:, -2:].sum(-1)
        best = np.argsort(-score, kind="stable")[:topk_group]
        inside = np.full_like(groups, -np.inf)
        inside[best] = groups[best]
        chosen.append(np.argsort(-inside.reshape(-1), kind="stable")[:k])
    return np.array(chosen)


def sown_choice(cfg, p, x):
    (_, _), state = jax.jit(lambda p, x: glm_moe.ExpertLayer(cfg).apply(
        {"params": p}, x, mutable=["intermediates"]))(p, x)
    return np.asarray(state["intermediates"]["chosen"][0])


def biased_scores(p, x):
    return jax.nn.sigmoid(x.reshape(-1, D) @ p["router"]["kernel"]) \
        + p["router"]["bias"]


@pytest.mark.parametrize("groups, top", [(4, 2), (4, 1), (2, 1), (8, 3)])
def test_the_grouped_choice_is_the_literal_loop(groups, top):
    """16 experts in ``groups`` groups of neighbours, the best ``top``
    kept: the layer's choice is the loop's, token by token, it lies
    inside ``top`` groups, and the layer is the dense masked sum over
    that choice (values and counts)."""
    cfg = config("sigmoid", n_group=groups, topk_group=top,
                 num_experts_per_tok=2, routed_scaling_factor=2.5)
    layer, p, x = layer_and_params(cfg)
    p["router"]["bias"] = jax.random.normal(jax.random.key(3),
                                            (EXPERTS,)) * 0.3
    got = sown_choice(cfg, p, x)
    want = literal_grouped_choice(biased_scores(p, x), groups, top, 2)
    np.testing.assert_array_equal(np.sort(got, -1), np.sort(want, -1))
    size = EXPERTS // groups
    assert all(len(set(row // size)) <= top for row in got)
    free = sown_choice(config("sigmoid", num_experts_per_tok=2), p, x)
    assert (np.sort(free, -1) != np.sort(got, -1)).any()

    def masked_dense(p, x):
        xf = x.reshape(-1, D)
        s = jax.nn.sigmoid(xf @ p["router"]["kernel"])
        picked = s * jnp.sum(jax.nn.one_hot(want, EXPERTS), axis=1)
        w = 2.5 * picked / jnp.sum(picked, -1, keepdims=True)
        swiglu = lambda g, u, d_: (nn.silu(xf @ g) * (xf @ u)) @ d_
        y = swiglu(*(p["shared"][n]["kernel"] for n in ("gate", "up", "down")))
        for j in range(HELD):
            y = y + w[:, OFFSET + j, None] * swiglu(
                p["experts"]["gate"][j], p["experts"]["up"][j],
                p["experts"]["down"][j])
        return y.reshape(x.shape)

    y, counts = jax.jit(layer.apply)({"params": p}, x)
    np.testing.assert_allclose(y, jax.jit(masked_dense)(p, x), **TOL)
    held = (want >= OFFSET) & (want < OFFSET + HELD)
    assert int(counts[:HELD].sum()) == int(held.sum())
    assert int(counts[glm_moe.DROPPED]) == 0


def test_a_group_whose_two_best_lose_is_never_chosen_from():
    """Expert 0 carries the largest ``score + bias`` of all, alone in
    its group: the group's two best sum to less than two full groups',
    so no token ever takes expert 0; a choice over all the experts
    takes it every time."""
    bias = np.zeros(EXPERTS, np.float32)
    bias[0], bias[1:4] = 3.0, -3.0          # group 0: one giant, three dwarfs
    bias[4:12] = 1.5                        # groups 1 and 2: all good
    cfg = config("sigmoid", n_group=GROUPS, topk_group=TOP_GROUPS)
    _, p, x = layer_and_params(cfg)
    p["router"]["bias"] = jnp.asarray(bias)
    scores = np.asarray(biased_scores(p, x))
    assert (scores.argmax(-1) == 0).all()
    got = sown_choice(cfg, p, x)
    assert not (got < 4).any() and ((got >= 4) & (got < 12)).all()
    assert (sown_choice(config("sigmoid"), p, x) == 0).any(axis=-1).all()


@pytest.mark.parametrize("scoring", ["sigmoid", "no_shared"])
def test_one_group_is_the_choice_as_it_was_bit_for_bit(scoring):
    """``n_group`` 1 hands the scores on untouched (the same array, no
    operation added to the step), so the choice is ``top_k(score +
    bias)`` to the bit; so is a choice among all of several groups."""
    cfg = config(scoring)
    assert (cfg.n_group, cfg.topk_group) == (1, 1) \
        == (qwen3next.Qwen3NextConfig().n_group,
            qwen3next.Qwen3NextConfig().topk_group)
    _, p, x = layer_and_params(cfg)
    p["router"]["bias"] = jax.random.normal(jax.random.key(3),
                                            (EXPERTS,)) * 0.3
    biased = biased_scores(p, x)
    assert glm_moe._within_best_groups(biased, 1, 1) is biased
    want = np.asarray(jax.lax.top_k(biased, K)[1])
    np.testing.assert_array_equal(sown_choice(cfg, p, x), want)
    every = dataclasses.replace(cfg, n_group=4, topk_group=4)
    np.testing.assert_array_equal(sown_choice(every, p, x), want)
    lowered = lambda c: jax.jit(glm_moe.ExpertLayer(c).apply).lower(
        {"params": p}, x).as_text()
    assert lowered(cfg) == lowered(dataclasses.replace(
        cfg, n_group=1, topk_group=3))


# -- the router --------------------------------------------------------------

# (experts, k, groups, of which the best): the four cells' routers
ROUTERS = [(512, 10, 1, 1), (512, 8, 8, 4), (64, 4, 1, 1)]
ROUTER_IDS = ["qwen3next", "ling3flash", "lfm2moe_and_glm47flash"]


def tied_scores(e, rows=256):
    """[rows, e] scores in quarters of one to two: every row full of
    ties."""
    return 1 + jnp.round(4 * jax.random.uniform(jax.random.key(e),
                                                (rows, e))) / 4


def sorted_choice(choice, n_group, topk_group, k):
    """The choice as it was made before PR 47: the sort that
    ``jax.lax.top_k`` is, twice more where the choice is limited to
    groups."""
    if n_group > 1:
        n, e = choice.shape
        grouped = choice.reshape(n, n_group, e // n_group)
        _, best = jax.lax.top_k(
            jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1), topk_group)
        kept = jnp.any(best[:, :, None] == jnp.arange(n_group), axis=1)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(n, e)
    return jax.lax.top_k(choice, k)[1]


@pytest.mark.parametrize("e, k, n_group, topk_group", ROUTERS, ids=ROUTER_IDS)
def test_the_rounds_of_max_choose_what_the_sort_chose_under_ties(
        e, k, n_group, topk_group):
    """``choose`` over scores full of ties: ``jax.lax.top_k``'s places
    in ``jax.lax.top_k``'s order (of equals the one that comes first),
    and the grouped choice is still the literal loop's."""
    choice = tied_scores(e)
    assert len(np.unique(choice)) <= 5

    @jax.jit
    def both(choice):
        within = glm_moe._within_best_groups(choice, n_group, topk_group)
        return (glm_moe.choose(choice, within, k)[0],
                sorted_choice(choice, n_group, topk_group, k))
    got, want = both(choice)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(
        got[:64], literal_grouped_choice(choice[:64], n_group, topk_group, k))


@pytest.mark.parametrize("e, k, n_group, topk_group", ROUTERS, ids=ROUTER_IDS)
def test_the_chosen_scores_and_their_gradients_are_the_gathers(
        e, k, n_group, topk_group):
    """``picked`` (what a round's reduction carried: the maximum itself
    where the choice is over the scores, the score beside the biased
    one otherwise) and its transpose (a comparison of places) against
    ``take_along_axis`` and its scatter-add, to the bit."""
    scores = tied_scores(e) / 4
    bias = None if n_group == 1 and e == 512 else \
        jnp.round(8 * jax.random.normal(jax.random.key(1), (e,))) / 32
    weight = jax.random.normal(jax.random.key(2), (scores.shape[0], k))

    def gates(picked):
        return jnp.sum(weight * picked / jnp.sum(picked, -1, keepdims=True))

    def by_rounds(scores):
        choice = None if bias is None else glm_moe._within_best_groups(
            jax.lax.stop_gradient(scores + bias), n_group, topk_group)
        return gates(glm_moe.choose(scores, choice, k)[1])

    def by_the_gather(scores):
        chosen = sorted_choice(scores if bias is None else scores + bias,
                               n_group, topk_group, k)
        return gates(jnp.take_along_axis(scores, chosen, axis=-1))

    got, want = jax.jit(lambda s: (jax.value_and_grad(by_rounds)(s),
                                   jax.value_and_grad(by_the_gather)(s))
                        )(scores)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert int((np.asarray(got[1]) != 0).sum()) == scores.shape[0] * k


@pytest.mark.parametrize("rows", ["float32", "bfloat16"])
def test_the_product_is_at_full_precision_whatever_the_rows(rows):
    """The router lowers to one product, float32 operands at
    ``Precision.HIGHEST`` (bfloat16 rows cast up: the three-pass form
    against the kernel's bfloat16 parts read no gain on the chip and
    went out again), and to no sort, gather or scatter."""
    import re
    cfg = config("softmax")
    arg = lambda *shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt)
    text = glm_moe.route.lower(arg(48, D, dt=jnp.dtype(rows)),
                               arg(D, EXPERTS), None, cfg).as_text()
    dots = re.findall(r"stablehlo\.dot_general.*", text)
    assert len(dots) == 1
    assert "precision = [HIGHEST, HIGHEST]" in dots[0]
    assert f"(tensor<48x{D}xf32>, tensor<{D}x{EXPERTS}xf32>)" in dots[0]
    assert "sort" not in text and "gather" not in text \
        and "scatter" not in text


LAYER_TREES = {
    "sigmoid": {"router/kernel": (D, EXPERTS), "router/bias": (EXPERTS,),
                "experts/gate": (HELD, D, WIDTH),
                "experts/up": (HELD, D, WIDTH),
                "experts/down": (HELD, WIDTH, D),
                "shared/gate/kernel": (D, WIDTH),
                "shared/up/kernel": (D, WIDTH),
                "shared/down/kernel": (WIDTH, D)},
    "softmax": {"router/kernel": (D, EXPERTS),
                "experts/gate": (HELD, D, WIDTH),
                "experts/up": (HELD, D, WIDTH),
                "experts/down": (HELD, WIDTH, D),
                "shared/gate/kernel": (D, 24), "shared/up/kernel": (D, 24),
                "shared/down/kernel": (24, D),
                "shared_gate/kernel": (D, 1)}}
# sha256 of the whole models' parameter shapes at their cells'
# rehearsal sizes, read on the parent commit (PR 38's tree)
MODEL_TREES = {"sigmoid": ("glm47flash-injit-1chip", "dde9e3c7cb5af6bd"),
               "softmax": ("qwen3next-injit-1chip", "7f7d11904d27bce3")}


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_older_models_parameter_trees_are_what_they_were(scoring):
    """The layer's leaves under ``GlmMoeConfig`` and ``Qwen3NextConfig``
    by name and shape, and the whole models' trees at the rehearsal
    sizes by the hash the parent commit gives."""
    from .chip_bench import _paths
    from chipbench import harness, weights
    _, p, _ = layer_and_params(config(scoring))
    got = {"/".join(str(k.key) for k in path): leaf.shape for path, leaf
           in jax.tree_util.tree_flatten_with_path(p)[0]}
    assert got == LAYER_TREES[scoring]
    cell, want = MODEL_TREES[scoring]
    spec = harness.resolve_cell(_paths.manifest(), cell, rehearse=True)
    family = harness.load_module("families", spec["config"]["family"])
    sz = family.sizes(spec["config"],
                      spec["config"]["assumed"]["per_chip_batch"])
    tree = json.dumps(weights.flat_shapes(jax.tree_util.tree_map(
        lambda a: tuple(a.shape),
        family.program_shapes(family.build_model(sz), sz))), sort_keys=True)
    assert hashlib.sha256(tree.encode()).hexdigest()[:16] == want


# -- a router that reads other rows than the experts (PR 49) ----------------

def _value_and_grads(fn, *args):
    weight = jax.random.normal(jax.random.key(4), args[-1].shape)
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a)[0] * weight),
        argnums=tuple(range(len(args)))))(*args)


@pytest.mark.parametrize("scoring", ["softmax", "no_shared"])
def test_a_router_that_reads_the_layers_own_rows_is_the_layer_as_it_was(
        scoring):
    """``router_input`` equal to the rows, as rows or as the
    ``Routing`` made of them ahead with the first tier's plan, and the
    default activation: value and every gradient to the bit of the
    call with neither (whose lowered step at the four cells' rehearsal
    sizes is the parent's, byte for byte: CHANGES.md, PR 49)."""
    cfg = config(scoring)
    assert cfg.expert_activation == "silu"
    layer, p, x = layer_and_params(cfg)
    plain = _value_and_grads(
        lambda p, x: layer.apply({"params": p}, x), p, x)
    same_rows = _value_and_grads(
        lambda p, x: layer.apply({"params": p}, x, x), p, x)

    def ahead(p, x):
        def routed_first(module, x):
            routing = module.route(x, plan_ahead=True)
            assert routing.plan is not None
            return module(x, routing)
        return nn.apply(routed_first, layer)({"params": p}, x)

    for other in (same_rows, _value_and_grads(ahead, p, x)):
        for a, b in zip(jax.tree_util.tree_leaves(plain),
                        jax.tree_util.tree_leaves(other)):
            np.testing.assert_array_equal(a, b)


def test_the_router_reads_its_own_rows_and_relu_gates_the_experts():
    """SmallThinker's layer: the choice and the weights from ``r``, the
    experts ``W_down (relu(W_gate u) * (W_up u))`` on ``u``; the
    router's gradient flows into ``r`` and the experts' into ``u``, and
    the plan made ahead changes nothing."""
    cfg = dataclasses.replace(config("no_shared"), scoring="softmax",
                              topk_weight_eps=0.0, expert_activation="relu")
    layer, p, u = layer_and_params(cfg)
    r = jax.random.normal(jax.random.key(9), u.shape)

    def by_hand(p, r, u):
        rf, uf = r.reshape(-1, D), u.reshape(-1, D)
        top, chosen = jax.lax.top_k(rf @ p["router"]["kernel"], K)
        w = jnp.sum(jax.nn.one_hot(chosen, EXPERTS)
                    * jax.nn.softmax(top, -1)[..., None], axis=1)
        e = p["experts"]
        return sum(w[:, OFFSET + j, None] * (
            (jax.nn.relu(uf @ e["gate"][j]) * (uf @ e["up"][j]))
            @ e["down"][j]) for j in range(HELD)).reshape(u.shape), None

    def ahead(p, r, u):
        return nn.apply(
            lambda module, r, u: module(u, module.route(r, plan_ahead=True)),
            layer)({"params": p}, r, u)

    want = _value_and_grads(by_hand, p, r, u)
    got = _value_and_grads(
        lambda p, r, u: layer.apply({"params": p}, u, r), p, r, u)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(_value_and_grads(ahead, p, r,
                                                               u))):
        np.testing.assert_array_equal(a, b)
    d_r, d_u = got[1][1], got[1][2]
    assert float(jnp.abs(d_r).max()) > 0 and float(jnp.abs(d_u).max()) > 0
    # SiLU in ReLU's place is another layer
    silu = layer.clone(cfg=dataclasses.replace(cfg, expert_activation="silu"))
    other = jax.jit(lambda p, r, u: silu.apply({"params": p}, u, r)[0])(
        p, r, u)
    assert float(jnp.abs(other - jax.jit(by_hand)(p, r, u)[0]).max()) > 1e-3
    with pytest.raises(ValueError, match="expert_activation"):
        jax.eval_shape(glm_moe.ExpertLayer(dataclasses.replace(
            cfg, expert_activation="gelu")).apply, {"params": p}, u)
