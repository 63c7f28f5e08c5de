"""Family ``glm_moe_lm``: a DeepSeek-V3-style sparse decoder as
GLM-4.7-Flash (``glm4_moe_lite``) lays it out, trained on next-token
cross-entropy plus the weighted multi-token one.

The program under test is ``horovod_tpu.models.glm_moe.GlmMoeLM`` with
``train_steps.glm_moe_train_step``; this file sizes it from a
configuration file, names its parameter shapes, makes its batch, counts
its FLOPs, and holds its plain float32 reference, which imports nothing
of the program.

What a later builder must know:

* **The reference's chain passes a pair.** ``check.StagedGradient``
  hands one activation from stage to stage; here it is ``(x,
  Emb(tokens))``. The multi-token module reads the embedding a second
  time at the end of the chain, and only through the pair does that
  use reach the ``first`` stage's backward. Blocks hand the second
  member on untouched. The ``last`` stage owns the final norm, the
  head and the whole multi-token module (its block included).
* **The layer holds a share of the experts** (``n_routed_experts`` in
  the configuration file is how many are held here; the router's width
  and the deployment are under ``deployment``). Program and reference
  alike leave out what the absent experts would add.
* **FLOPs depend on routing.** ``flops_per_sample`` counts routed
  experts by their expectation, ``num_experts_per_tok x held /
  router width`` experts a token (0.5 here), not by what a batch really
  routes: the family's count takes shapes alone. The grouped products'
  own readers (``layer_metrics/moe_grouped_*.py``) use the assignments
  the program counted.
* **The readers of this family match kernels by name**
  (``KERNEL_NAMES``), not every ``tpu_custom_call``: the step has the
  flash kernels and the compiler's grouped-matmul kernels side by side.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import weights

SAMPLE = "tokens"

# How the device's ops line names the kernels (trace_reduce.short_name):
# the flash kernels by their Pallas ``name=``, the grouped products by
# the instruction the TPU compiler lowers ``jax.lax.ragged_dot`` to.
KERNEL_NAMES = {
    "flash": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
    "grouped": ("ragged-dot",),
}


def sizes(config: dict, per_chip_batch: int) -> dict:
    dep = config["deployment"]
    return {
        "vocab": config["vocab_size"],
        "layers": config["num_hidden_layers"],
        "dense_layers": config["first_k_dense_replace"],
        "mtp_layers": config["num_nextn_predict_layers"],
        "d": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"], "kv_rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "head_dim": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        "mlp": config["intermediate_size"],
        "expert_mlp": config["moe_intermediate_size"],
        "experts": dep["router_width"],
        "experts_held": config["n_routed_experts"],
        "expert_offset": dep["expert_offset"],
        "top_k": config["num_experts_per_tok"],
        "scale": float(config["routed_scaling_factor"]),
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "mtp_weight": float(config["assumed"]["mtp_loss_weight"]),
        "seq": config["assumed"]["sequence_length"],
        "per_chip_batch": per_chip_batch}


def samples_per_row(sz: dict) -> int:
    return sz["seq"]


def attention_layers(sz: dict) -> int:
    return sz["layers"] + sz["mtp_layers"]


def expert_layers(sz: dict) -> int:
    return sz["layers"] - sz["dense_layers"] + sz["mtp_layers"]


def _attn_params(sz: dict) -> int:
    d, h = sz["d"], sz["heads"]
    return (d * sz["q_rank"] + sz["q_rank"] * h * sz["head_dim"]
            + d * (sz["kv_rank"] + sz["rope"])
            + sz["kv_rank"] * h * (sz["nope"] + sz["v_dim"])
            + h * sz["v_dim"] * d)


def _swiglu_params(sz: dict, width: int) -> int:
    return 3 * sz["d"] * width


def param_count(sz: dict) -> int:
    """Every parameter the chip holds, norm scales and the router's
    bias included."""
    shapes, _ = param_shapes(sz)
    return sum(math.prod(shape) for shape in
               weights.flat_shapes(shapes["params"]).values())


def matmul_params_per_token(sz: dict) -> float:
    """Matmul parameters a token meets in one step: attention in every
    layer, the dense MLP, the shared expert and the router in every
    expert layer, ``top_k x held / experts`` routed experts there by
    expectation, the multi-token projection, and the head twice (the
    main loss and the multi-token one). The embedding is a gather."""
    routed = sz["top_k"] * sz["experts_held"] / sz["experts"]
    expert_layer = (_swiglu_params(sz, sz["expert_mlp"]) * (1 + routed)
                    + sz["d"] * sz["experts"])
    return (attention_layers(sz) * _attn_params(sz)
            + sz["dense_layers"] * _swiglu_params(sz, sz["mlp"])
            + expert_layers(sz) * expert_layer
            + sz["mtp_layers"] * 2 * sz["d"] * sz["d"]
            + (1 + sz["mtp_layers"]) * sz["d"] * sz["vocab"])


def flops_per_sample(sz: dict) -> float:
    """The PaLM count: 6 per matmul parameter a token meets plus
    12 L S (heads x head size) for attention, the full causal square."""
    return 6.0 * matmul_params_per_token(sz) \
        + 12.0 * attention_layers(sz) * sz["seq"] \
        * sz["heads"] * sz["head_dim"]


def _block_shapes(sz: dict, moe: bool, prefix: str):
    d, h = sz["d"], sz["heads"]
    attn = {"q_a": {"kernel": (d, sz["q_rank"])},
            "q_norm": {"scale": (sz["q_rank"],)},
            "q_b": {"kernel": (sz["q_rank"], h, sz["head_dim"])},
            "kv_a": {"kernel": (d, sz["kv_rank"] + sz["rope"])},
            "kv_norm": {"scale": (sz["kv_rank"],)},
            "kv_b": {"kernel": (sz["kv_rank"], h, sz["nope"] + sz["v_dim"])},
            "o": {"kernel": (h, sz["v_dim"], d)}}
    fan = {f"{prefix}/attn/q_a/kernel": d,
           f"{prefix}/attn/q_b/kernel": sz["q_rank"],
           f"{prefix}/attn/kv_a/kernel": d,
           f"{prefix}/attn/kv_b/kernel": sz["kv_rank"],
           f"{prefix}/attn/o/kernel": h * sz["v_dim"]}

    def swiglu(width, at):
        fan.update({f"{at}/gate/kernel": d, f"{at}/up/kernel": d,
                    f"{at}/down/kernel": width})
        return {"gate": {"kernel": (d, width)}, "up": {"kernel": (d, width)},
                "down": {"kernel": (width, d)}}

    block = {"attn": attn, "ln1": {"scale": (d,)}, "ln2": {"scale": (d,)}}
    if moe:
        w, held = sz["expert_mlp"], sz["experts_held"]
        block["moe"] = {
            "router": {"kernel": (d, sz["experts"]),
                       "bias": (sz["experts"],)},
            "experts": {"gate": (held, d, w), "up": (held, d, w),
                        "down": (held, w, d)},
            "shared": swiglu(w, f"{prefix}/moe/shared")}
        fan.update({f"{prefix}/moe/router/kernel": d,
                    f"{prefix}/moe/experts/gate": d,
                    f"{prefix}/moe/experts/up": d,
                    f"{prefix}/moe/experts/down": w})
    else:
        block["mlp"] = swiglu(sz["mlp"], f"{prefix}/mlp")
    return block, fan


def param_shapes(sz: dict):
    """(``{"params": ..., "aux": {}}`` as nested shapes, fan-ins by
    path)."""
    d = sz["d"]
    params = {"embed": {"embedding": (sz["vocab"], d)},
              "lm_head": {"kernel": (d, sz["vocab"])},
              "norm_f": {"scale": (d,)}}
    fan = {"params/embed/embedding": d, "params/lm_head/kernel": d}
    for i in range(sz["layers"]):
        params[f"block_{i}"], f = _block_shapes(
            sz, i >= sz["dense_layers"], f"params/block_{i}")
        fan.update(f)
    if sz["mtp_layers"]:
        block, f = _block_shapes(sz, True, "params/mtp/block")
        fan.update(f)
        fan["params/mtp/proj/kernel"] = 2 * d
        params["mtp"] = {"norm_e": {"scale": (d,)}, "norm_h": {"scale": (d,)},
                         "proj": {"kernel": (2 * d, d)}, "block": block,
                         "norm_f": {"scale": (d,)}}
    return {"params": params, "aux": {}}, fan


def make_batch(sz: dict, rows: int):
    """``key -> (tokens,)``: ``rows`` sequences of random ids from the
    chip's slice of the vocabulary, every row different."""
    def batch(key):
        return (jax.random.randint(key, (rows, sz["seq"]), 0, sz["vocab"],
                                   jnp.int32),)
    return batch


# -- the program ----------------------------------------------------------

def build_model(sz: dict):
    from horovod_tpu.models.glm_moe import GlmMoeConfig, GlmMoeLM
    return GlmMoeLM(GlmMoeConfig(
        vocab_size=sz["vocab"], num_layers=sz["layers"],
        first_k_dense=sz["dense_layers"], hidden_size=sz["d"],
        num_heads=sz["heads"], q_lora_rank=sz["q_rank"],
        kv_lora_rank=sz["kv_rank"], qk_nope_head_dim=sz["nope"],
        qk_rope_head_dim=sz["rope"], v_head_dim=sz["v_dim"],
        intermediate_size=sz["mlp"], moe_intermediate_size=sz["expert_mlp"],
        n_routed_experts=sz["experts"],
        num_experts_per_tok=sz["top_k"],
        routed_scaling_factor=sz["scale"],
        experts_held=sz["experts_held"], expert_offset=sz["expert_offset"],
        mtp_layers=sz["mtp_layers"], mtp_loss_weight=sz["mtp_weight"],
        rms_norm_eps=sz["eps"], rope_theta=sz["theta"],
        dtype=jnp.bfloat16))


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
    return train_steps.glm_moe_train_step(model, tx, mesh)


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
    loss_fn = train_steps.glm_moe_loss_fn(model)
    return lambda params, aux, tokens: (loss_fn(params, tokens)[0], aux)


# -- the plain reference --------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


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


def _swiglu(p, x):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def reference_fns(sz: dict) -> dict:
    """The model's parts in float32, each a plain function of its own
    parameters: ``mla``, ``routing``, ``expert_layer`` (with
    ``held``/``offset`` to ask for another share, or all the experts),
    ``block``, ``head_loss``, ``mtp_input``, ``mtp_hidden``. The tests
    hold the program's modules against them one by one."""
    eps, theta = sz["eps"], sz["theta"]
    nope, heads, head_dim = sz["nope"], sz["heads"], sz["head_dim"]

    @jax.checkpoint
    def attend_one(qkv):
        q, k, v = qkv                                     # [S, D] a head
        logits = (q @ k.T) / math.sqrt(head_dim)
        s = q.shape[0]
        mask = jnp.tril(jnp.ones((s, s), bool))
        return jax.nn.softmax(jnp.where(mask, logits, -1e30), -1) @ v

    def mla(p, x):
        b, s, _ = x.shape
        c_q = _rms(x @ p["q_a"]["kernel"], p["q_norm"]["scale"], eps)
        q = jnp.einsum("bsr,rhe->bshe", c_q, p["q_b"]["kernel"])
        kv = x @ p["kv_a"]["kernel"]
        c_kv = _rms(kv[..., :sz["kv_rank"]], p["kv_norm"]["scale"], eps)
        k_rope = _rope(kv[..., None, sz["kv_rank"]:], theta)   # one, shared
        kv = jnp.einsum("bsr,rhe->bshe", c_kv, p["kv_b"]["kernel"])
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(
                k_rope, (b, s, heads, sz["rope"]))], -1)
        v = kv[..., nope:]
        # a row and a head at a time
        flat = lambda t: t.transpose(0, 2, 1, 3).reshape(
            b * heads, s, t.shape[-1])
        out = jax.lax.map(attend_one, (flat(q), flat(k), flat(v)))
        out = out.reshape(b, heads, s, -1).transpose(0, 2, 1, 3)
        return jnp.einsum("bshe,hed->bsd", out, p["o"]["kernel"])

    def routing(p, x):
        """``(weights [N, experts], chosen [N, top_k])`` of the tokens
        ``x`` [N, d]: the weight of an expert not chosen is zero."""
        scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
        _, chosen = jax.lax.top_k(scores + p["router"]["bias"], sz["top_k"])
        mask = jnp.sum(jax.nn.one_hot(chosen, sz["experts"]), axis=1)
        picked = scores * mask
        return (sz["scale"] * picked
                / jnp.sum(picked, -1, keepdims=True)), chosen

    def expert_layer(p, x, held=None, offset=None, shared=True):
        """The experts [offset, offset + held) as a plain loop over all
        the tokens, each weighted by its router weight (zero where the
        token did not choose it), plus the shared expert. ``p`` holds
        ``held`` experts' kernels."""
        held = sz["experts_held"] if held is None else held
        offset = sz["expert_offset"] if offset is None else offset
        xf = x.reshape(-1, x.shape[-1])
        weights, _ = routing(p, xf)

        @jax.checkpoint
        def one(xf, gate, up, down, w):
            return w[:, None] * ((jax.nn.silu(xf @ gate) * (xf @ up)) @ down)

        y = _swiglu(p["shared"], xf) if shared else jnp.zeros_like(xf)
        for j in range(held):
            y = y + one(xf, p["experts"]["gate"][j], p["experts"]["up"][j],
                        p["experts"]["down"][j], weights[:, offset + j])
        return y.reshape(x.shape)

    def block(p, x, chosen=False):
        """The block's output, or with ``chosen`` what its router
        chose, [tokens, top_k]."""
        x = x + mla(p["attn"], _rms(x, p["ln1"]["scale"], eps))
        h = _rms(x, p["ln2"]["scale"], eps)
        if chosen:
            return routing(p["moe"], h.reshape(-1, h.shape[-1]))[1]
        return x + (expert_layer(p["moe"], h) if "moe" in p
                    else _swiglu(p["mlp"], h))

    def head_loss(head, x, targets):
        """Mean cross-entropy of ``x`` [B, T, d] against ``targets``
        [B, T], a row at a time."""
        @jax.checkpoint
        def row_ll(xs):
            hidden, t = xs
            logp = jax.nn.log_softmax(hidden @ head, -1)
            return jnp.sum(jnp.take_along_axis(logp, t[:, None], -1))

        return -jnp.sum(jax.lax.map(row_ll, (x, targets))) / targets.size

    def mtp_input(p, x, embedded):
        """Position i: ``W_eh [norm_e(Emb(t_{i+1})), norm_h(h_i)]``.
        ``embedded`` is ``Emb(tokens)``; the last position reads the
        first token, has no target, and no earlier position attends to
        it."""
        joined = jnp.concatenate(
            [_rms(jnp.roll(embedded, -1, axis=1), p["norm_e"]["scale"], eps),
             _rms(x, p["norm_h"]["scale"], eps)], -1)
        return joined @ p["proj"]["kernel"]

    def mtp_hidden(p, x, embedded):
        """The module's input through its own block and final norm."""
        return _rms(block(p["block"], mtp_input(p, x, embedded)),
                    p["norm_f"]["scale"], eps)

    return {"mla": mla, "routing": routing, "expert_layer": expert_layer,
            "block": block, "head_loss": head_loss, "mtp_input": mtp_input,
            "mtp_hidden": mtp_hidden}


def reference_stages(sz: dict) -> dict:
    """The plain model as stages for ``check.StagedGradient``. The
    activation handed along the chain is the pair ``(x, Emb(tokens))``
    (module docstring)."""
    fns = reference_fns(sz)
    eps = sz["eps"]

    def embed(p, aux, tokens):
        x = p["embed"]["embedding"][tokens]
        return (x, x), {}

    def block(p, aux, pair):
        x, embedded = pair
        return (fns["block"](p, x), embedded), {}

    def last(p, pair, tokens):
        x, embedded = pair
        head = p["lm_head"]["kernel"]
        loss = fns["head_loss"](
            head, _rms(x, p["norm_f"]["scale"], eps)[:, :-1], tokens[:, 1:])
        if sz["mtp_layers"]:
            hidden = fns["mtp_hidden"](p["mtp"], x, embedded)
            loss = loss + sz["mtp_weight"] * fns["head_loss"](
                head, hidden[:, :-2], tokens[:, 2:])
        return loss

    return {"first": (("embed",), embed),
            "blocks": [(f"block_{i}", block) for i in range(sz["layers"])],
            "last": (("norm_f", "lm_head", "mtp"), last)}
