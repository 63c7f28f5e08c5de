"""Family ``smallthinker_lm``: a local-global sparse decoder as
SmallThinker-21BA3B (``smallthinker``) lays it out: grouped-head
attention, windowed with a rotary in three layers of four and full
without any positional signal in the fourth; in every layer an expert
layer (a softmax router that reads the block's input before attention,
ReLU-gated experts, no shared expert); trained on next-token
cross-entropy through an untied head.

The program under test is
``horovod_tpu.models.smallthinker.SmallThinkerLM`` with
``train_steps.smallthinker_train_step``; this file sizes it from a
configuration file, names its parameter shapes, makes its batch, counts
its FLOPs, and holds its plain float32 reference, which imports nothing
of the program.

What a later builder must know:

* **The kept layers carry their published index** (``kept_layers`` in
  the configuration file): layer ``i`` has a window where the published
  ``sliding_window_layout[i]`` is 1 and a rotary where ``rope_layout[i]``
  is 1 (each list read for itself), and its parameters are under
  ``layer_<i>``.
* **The router reads the block's input**, ``r = x W_r`` ahead of both
  norms, and the experts the post-attention normalised states
  (``assumed.router_input`` in the configuration file says why). The
  reference's block computes the logits first, as the published block
  does, though nothing in plain ``jax.numpy`` depends on the order.
* **The layer holds a share of the experts**
  (``moe_num_primary_experts`` in the configuration file is how many are
  held here; the router's width and the deployment are under
  ``deployment``). Program and reference alike leave out what the
  absent experts would add, and there is no shared expert: a token that
  chose no held expert gets zero from the layer.
* **Attention is the dense masked softmax a block of queries at a
  time**, the mask ``0 <= t - s < window`` written out; the expert layer
  a masked dense ReLU-gated product an expert over a block of tokens at
  a time; a block runs a row of the batch at a time.
* **FLOPs** count attention by the scores the masks allow (three bands
  and a causal half at the cell's size) and the routed experts by their
  expectation, ``top_k x held / router width`` experts a token (1.5
  here).
* **The readers of this family match kernels by name**
  (``KERNEL_NAMES``); the flash kernels' own readers here
  (``swa_flash_*``) count the bands and the half, k and v by key-value
  head (``chipbench/smallthinker_flops.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import smallthinker_flops, weights

SAMPLE = "tokens"

# How the device's ops line names the kernels (trace_reduce.short_name):
# the flash kernels by their Pallas ``name=``, the grouped products by
# the instruction the TPU compiler lowers ``jax.lax.ragged_dot`` to.
KERNEL_NAMES = {
    "flash": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
    "grouped": ("ragged-dot",),
}

ROWS_AT_A_TIME = 2048     # of the reference's position-wise parts


def sizes(config: dict, per_chip_batch: int) -> dict:
    dep, assumed = config["deployment"], config["assumed"]
    kept = tuple(config["kept_layers"])
    if len(kept) != config["num_hidden_layers"]:
        raise ValueError(f"{len(kept)} kept layers {kept} against "
                         f"num_hidden_layers {config['num_hidden_layers']}")
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]) or config["tie_word_embeddings"] \
            or config["rope_scaling"] is not None:
        raise ValueError("the family is a softmax over the chosen logits, "
                         "an untied head and an unscaled rotary")
    return {
        "vocab": config["vocab_size"],
        "d": config["hidden_size"],
        "kept": kept,
        "window_layout": tuple(config["sliding_window_layout"]),
        "rope_layout": tuple(config["rope_layout"]),
        "window": config["sliding_window_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "theta": float(config["rope_theta"]),
        "expert_mlp": config["moe_ffn_hidden_size"],
        "experts": dep["router_width"],
        "experts_held": config["moe_num_primary_experts"],
        "expert_offset": dep["expert_offset"],
        "top_k": config["moe_num_active_primary_experts"],
        "eps": float(config["rms_norm_eps"]),
        "seq": assumed["sequence_length"],
        "per_chip_batch": per_chip_batch}


def samples_per_row(sz: dict) -> int:
    return sz["seq"]


def expert_layers(sz: dict) -> int:
    return len(sz["kept"])


def matmul_params_per_token(sz: dict) -> float:
    """Matmul parameters a token meets in one step: each layer's four
    projections, its router and ``top_k x held / experts`` routed
    experts by expectation, and the head (the embedding is a gather)."""
    d, h, kv, hd = sz["d"], sz["heads"], sz["kv_heads"], sz["head_dim"]
    routed = sz["top_k"] * sz["experts_held"] / sz["experts"]
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d \
        + d * sz["experts"] + routed * 3 * d * sz["expert_mlp"]
    return len(sz["kept"]) * layer + d * sz["vocab"]


def param_count(sz: dict) -> int:
    shapes, _ = param_shapes(sz)
    return sum(math.prod(shape) for shape in
               weights.flat_shapes(shapes["params"]).values())


def flops_per_sample(sz: dict) -> float:
    """A token's share of a training step: 6 per matmul parameter it
    meets; attention by the scores the masks allow (two products
    forward, three forwards' worth)."""
    return 6.0 * matmul_params_per_token(sz) \
        + smallthinker_flops.attention_flops_per_token(sz)


def param_shapes(sz: dict):
    """(``{"params": ..., "aux": {}}`` as nested shapes, fan-ins by
    path). Norm scales are ones (``weights.leaf_value``), as the family
    starts them."""
    d, w, held = sz["d"], sz["expert_mlp"], sz["experts_held"]
    h, kv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    params = {"embed": {"embedding": (sz["vocab"], d)},
              "norm_f": {"scale": (d,)},
              "lm_head": {"kernel": (d, sz["vocab"])}}
    # The embedding's rows at unit variance (a fan-in of 1; every other
    # kernel at its fan-in): at d^-0.5 the seeded residual stream is
    # the sub-layers' outputs, which uniform attention makes alike for
    # all tokens; by layer 3 every token chose the same experts and a
    # layer's held assignments followed the seed by a third (PERF.md).
    fan = {"params/embed/embedding": 1, "params/lm_head/kernel": d}
    for i in sz["kept"]:
        at = f"params/layer_{i}"
        params[f"layer_{i}"] = {
            "attention_norm": {"scale": (d,)}, "ffn_norm": {"scale": (d,)},
            "attention": {"q": {"kernel": (d, h, hd)},
                          "k": {"kernel": (d, kv, hd)},
                          "v": {"kernel": (d, kv, hd)},
                          "o": {"kernel": (h, hd, d)}},
            "moe": {"router": {"kernel": (d, sz["experts"])},
                    "experts": {"gate": (held, d, w), "up": (held, d, w),
                                "down": (held, w, d)}}}
        fan.update({f"{at}/attention/q/kernel": d,
                    f"{at}/attention/k/kernel": d,
                    f"{at}/attention/v/kernel": d,
                    f"{at}/attention/o/kernel": h * hd,
                    f"{at}/moe/router/kernel": d,
                    f"{at}/moe/experts/gate": d, f"{at}/moe/experts/up": d,
                    f"{at}/moe/experts/down": w})
    return {"params": params, "aux": {}}, fan


def make_batch(sz: dict, rows: int):
    """``key -> (tokens,)``: ``rows`` sequences of random ids from the
    chip's slice of the vocabulary."""
    def batch(key):
        return (jax.random.randint(key, (rows, sz["seq"]), 0, sz["vocab"],
                                   jnp.int32),)
    return batch


# -- the program ----------------------------------------------------------

def build_model(sz: dict):
    from horovod_tpu.models.smallthinker import (
        SmallThinkerConfig, SmallThinkerLM,
    )
    return SmallThinkerLM(SmallThinkerConfig(
        vocab_size=sz["vocab"], hidden_size=sz["d"],
        sliding_window_layout=sz["window_layout"],
        rope_layout=sz["rope_layout"], kept_layers=sz["kept"],
        num_heads=sz["heads"], num_kv_heads=sz["kv_heads"],
        head_dim=sz["head_dim"], sliding_window_size=sz["window"],
        rope_theta=sz["theta"], moe_intermediate_size=sz["expert_mlp"],
        n_routed_experts=sz["experts"], num_experts_per_tok=sz["top_k"],
        experts_held=sz["experts_held"], expert_offset=sz["expert_offset"],
        rms_norm_eps=sz["eps"], dtype=jnp.bfloat16))


def program_shapes(model, sz: dict):
    tree = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, sz["seq"]), jnp.int32)),
        jax.random.key(0))
    return {"params": tree["params"], "aux": {}}


_feed = None    # the host's end of the program's load counters


def injit_step(model, tx, mesh):
    from horovod_tpu.models import train_steps
    global _feed
    _feed = train_steps.MoeLoadFeed()
    return train_steps.smallthinker_train_step(model, tx, mesh)


def injit_args(state: dict, batch: tuple) -> tuple:
    return (state["params"], state["opt"], *batch)


def injit_unpack(out, state: dict):
    """The step's counts go to the program's feed as the device array
    they are: it never waits for a step."""
    params, opt, loss, counts = out
    _feed.push(counts)
    return {"params": params, "aux": state["aux"], "opt": opt}, loss


def program_loss(model):
    from horovod_tpu.models import train_steps
    loss_fn = train_steps.smallthinker_loss_fn(model)
    return lambda params, aux, tokens: (loss_fn(params, tokens)[0], aux)


# -- the plain reference --------------------------------------------------

def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * p["scale"]


def _rope(x, theta):
    """x: [B, S, H, R], rotary over all of R, halves paired."""
    s, r = x.shape[1], x.shape[-1]
    half = r // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _by_rows(fn, x, rows=ROWS_AT_A_TIME):
    """``fn`` over the rows of ``x`` [..., width], a block of rows at a
    time, each block recomputed in the backward pass."""
    flat = x.reshape(-1, x.shape[-1])
    n = flat.shape[0]
    rows = rows if n % rows == 0 else n
    out = jax.lax.map(jax.checkpoint(fn),
                      flat.reshape(n // rows, rows, -1))
    return out.reshape(*x.shape[:-1], out.shape[-1])


def reference_fns(sz: dict) -> dict:
    """The model's parts in float32, each a plain function of its own
    parameters: ``attend``, ``attention``, ``routing``, ``expert_layer``
    (with ``held``/``offset`` to ask for another share, or all the
    experts), ``block``, ``head_loss``. The tests hold the program's
    modules against them one by one."""
    eps, d = sz["eps"], sz["d"]

    def attend(q, k, v, window=None):
        """softmax(q k^T / sqrt(d) + mask) v, dense: q [B, S, H, D];
        k, v [B, S, Hkv, D]; query head h reads key-value head h // (H
        / Hkv); the query at ``t`` sees the key at ``s`` where ``0 <= t
        - s`` and, with a ``window``, ``t - s < window``. A block of
        queries at a time."""
        bt, seq, heads, hd = q.shape
        group = heads // k.shape[2]
        rows = ROWS_AT_A_TIME if seq % ROWS_AT_A_TIME == 0 else seq
        positions = jnp.arange(seq)

        @jax.checkpoint
        def one(args):
            qi, ki, vi, start = args
            behind = (start + jnp.arange(rows))[:, None] - positions[None, :]
            allowed = behind >= 0
            if window is not None:
                allowed = allowed & (behind < window)
            scores = jnp.where(allowed, (qi @ ki.T) / math.sqrt(hd), -1e30)
            return jax.nn.softmax(scores, -1) @ vi

        def head(args):
            qh, kh, vh = args
            return jax.lax.map(
                lambda a: one((a[0], kh, vh, a[1])),
                (qh.reshape(seq // rows, rows, hd),
                 jnp.arange(0, seq, rows))).reshape(seq, -1)

        flat = lambda t: t.transpose(0, 2, 1, 3).reshape(
            -1, seq, t.shape[-1])
        out = jax.lax.map(head, (
            flat(q), flat(jnp.repeat(k, group, 2)),
            flat(jnp.repeat(v, group, 2))))
        return out.reshape(bt, heads, seq, -1).transpose(0, 2, 1, 3)

    def attention(p, x, index):
        """Published layer ``index``'s: the rotary where ``rope_layout``
        says so, the window where ``sliding_window_layout`` does."""
        q = jnp.einsum("bsd,dhe->bshe", x, p["q"]["kernel"])
        k = jnp.einsum("bsd,dhe->bshe", x, p["k"]["kernel"])
        v = jnp.einsum("bsd,dhe->bshe", x, p["v"]["kernel"])
        if sz["rope_layout"][index]:
            q, k = _rope(q, sz["theta"]), _rope(k, sz["theta"])
        out = attend(q, k, v,
                     sz["window"] if sz["window_layout"][index] else None)
        return jnp.einsum("bshe,hed->bsd", out, p["o"]["kernel"])

    def routing(p, x):
        """``(weights [N, experts], chosen [N, top_k])`` of the rows
        ``x`` [N, d] the router reads: the ``top_k`` largest logits,
        a softmax over those; the weight of an expert not chosen is
        zero."""
        top, chosen = jax.lax.top_k(x @ p["router"]["kernel"], sz["top_k"])
        return jnp.sum(jax.nn.one_hot(chosen, sz["experts"])
                       * jax.nn.softmax(top, -1)[..., None], axis=1), chosen

    def expert_layer(p, x, routed, held=None, offset=None):
        """The experts [offset, offset + held) one after another on the
        rows ``x``, each ``W_down (relu(W_gate u) * (W_up u))`` weighted
        by its router weight, which comes from the rows ``routed`` (zero
        where the token did not choose it), over a block of tokens at a
        time. No shared expert. ``p`` holds ``held`` experts' kernels."""
        held = sz["experts_held"] if held is None else held
        offset = sz["expert_offset"] if offset is None else offset
        xf = x.reshape(-1, d)
        share = _by_rows(lambda t: routing(p, t)[0], routed) \
            .reshape(-1, sz["experts"])[:, offset:offset + held]
        experts = (p["experts"]["gate"], p["experts"]["up"],
                   p["experts"]["down"])

        def rows(t):
            xs, ws = t[:, :d], t[:, d:]

            def one(y, e):
                gate, up, down, w = e
                return y + w[:, None] * (
                    (jax.nn.relu(xs @ gate) * (xs @ up)) @ down), None

            return jax.lax.scan(one, jnp.zeros_like(xs),
                                (*experts, ws.T))[0]

        return _by_rows(rows, jnp.concatenate([xf, share], -1)) \
            .reshape(x.shape)

    def block(p, index, x, chosen=False):
        """Published layer ``index``; with ``chosen`` what its router
        chose, [tokens, top_k]."""
        if chosen:
            return routing(p["moe"], x.reshape(-1, d))[1]
        h = x + attention(p["attention"], _rms(x, p["attention_norm"], eps),
                          index)
        return h + expert_layer(p["moe"], _rms(h, p["ffn_norm"], eps), x)

    def head_loss(kernel, x, targets):
        """Mean cross-entropy of ``x`` [B, T, d] against ``targets``
        [B, T] with the logits ``x kernel``, a block of rows at a
        time."""
        flat, t = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
        n = flat.shape[0]
        rows = min(ROWS_AT_A_TIME, n)
        pad = (-n) % rows
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
        t = jnp.pad(t, (0, pad))
        live = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad))

        @jax.checkpoint
        def rows_ll(xs):
            hidden, ids, w = xs
            logp = jax.nn.log_softmax(hidden @ kernel, -1)
            return jnp.sum(
                jnp.take_along_axis(logp, ids[:, None], -1)[:, 0] * w)

        split = lambda a: a.reshape(-1, rows, *a.shape[1:])
        return -jnp.sum(jax.lax.map(
            rows_ll, (split(flat), split(t), split(live)))) / n

    return {"attend": attend, "attention": attention, "routing": routing,
            "expert_layer": expert_layer, "block": block,
            "head_loss": head_loss}


def reference_stages(sz: dict) -> dict:
    """The plain model as stages for ``check.StagedGradient``: the
    activation handed along the chain is the residual alone."""
    fns = reference_fns(sz)

    def embed(p, aux, tokens):
        return p["embed"]["embedding"][tokens], {}

    def block_of(index):
        def block(p, aux, x):
            """A row of the batch at a time, recomputed in the backward
            pass: rows meet nowhere before the loss."""
            one_row = jax.checkpoint(
                lambda row: fns["block"](p, index, row[None])[0])
            return jax.lax.map(one_row, x), {}
        return block

    def last(p, x, tokens):
        hidden = _rms(x, p["norm_f"], sz["eps"])
        return fns["head_loss"](p["lm_head"]["kernel"], hidden[:, :-1],
                                tokens[:, 1:])

    return {"first": (("embed",), embed),
            "blocks": [(f"layer_{i}", block_of(i)) for i in sz["kept"]],
            "last": (("norm_f", "lm_head"), last)}
