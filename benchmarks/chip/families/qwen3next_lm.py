"""Family ``qwen3next_lm``: a hybrid linear-attention sparse decoder as
Qwen3-Next (``qwen3_next``) lays it out: Gated DeltaNet in three layers
of four, gated softmax attention in the fourth, an expert layer (a
softmax router, a gated shared expert) in every one; trained on
next-token cross-entropy through an untied head.

The program under test is ``horovod_tpu.models.qwen3next.Qwen3NextLM``
with ``train_steps.qwen3next_train_step``; this file sizes it from a
configuration file, names its parameter shapes, makes its batch, counts
its FLOPs, and holds its plain float32 reference, which imports nothing
of the program.

What a later builder must know:

* **The kept layers carry their published index** (``kept_layers`` in
  the configuration file): layer ``i`` is gated attention where ``(i +
  1) mod full_attention_interval = 0`` and Gated DeltaNet otherwise,
  and its parameters are under ``layer_<i>``.
* **The layer holds a share of the experts** (``num_experts`` in the
  configuration file is how many are held here; the router's width and
  the deployment are under ``deployment``). Program and reference alike
  leave out what the absent experts would add.
* **The rule is a literal ``lax.scan`` over time** (one matrix state a
  value head, one position a step), nested by chunk under
  ``jax.checkpoint`` so that its backward fits; attention is the dense
  softmax, a block of queries at a time; the expert layer is a masked
  dense SwiGLU an expert, over all the tokens; position-wise parts run
  a block of rows at a time under ``jax.checkpoint``.
* **FLOPs** count attention by the causal half, the rule by its
  recurrence (``chipbench/gdn_flops.py``: 7 operations a state entry
  and position, whatever chunked algorithm a kernel runs) and the routed
  experts by their expectation, ``num_experts_per_tok x held / router
  width`` experts a token (0.625 here).
* **The readers of this family match kernels by name**
  (``KERNEL_NAMES``); the readers written for the first sparse family
  (``moe_grouped_*``, ``mla_flash_*``) read ``sz`` and these names.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import gdn_flops, weights

SAMPLE = "tokens"

# How the device's ops line names the kernels (trace_reduce.short_name):
# the Pallas kernels by their ``name=``, the grouped products by the
# instruction the TPU compiler lowers ``jax.lax.ragged_dot`` to.
KERNEL_NAMES = {
    "flash": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
    "grouped": ("ragged-dot",),
    "gdn": ("gdn_fwd", "gdn_bwd"),
}

ROWS_AT_A_TIME = 2048     # of the reference's position-wise parts
REFERENCE_CHUNK = 128     # of its recurrence: checkpoints between chunks


def sizes(config: dict, per_chip_batch: int) -> dict:
    dep, gates = config["deployment"], config["assumed"]["gates"]
    kept = tuple(config["kept_layers"])
    if len(kept) != config["num_hidden_layers"]:
        raise ValueError(f"{len(kept)} kept layers {kept} against "
                         f"num_hidden_layers {config['num_hidden_layers']}")
    return {
        "vocab": config["vocab_size"],
        "d": config["hidden_size"],
        "kept": kept,
        "published_layers": config["published"]["num_hidden_layers"],
        "interval": config["full_attention_interval"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rotary": int(config["head_dim"] * config["partial_rotary_factor"]),
        "theta": float(config["rope_theta"]),
        "key_heads": config["linear_num_key_heads"],
        "value_heads": config["linear_num_value_heads"],
        "key_dim": config["linear_key_head_dim"],
        "value_dim": config["linear_value_head_dim"],
        "conv": config["linear_conv_kernel_dim"],
        "a_log_init": float(gates["a_log_init"]),
        "dt_bias_init": float(gates["dt_bias_init"]),
        "expert_mlp": config["moe_intermediate_size"],
        "shared_mlp": config["shared_expert_intermediate_size"],
        "experts": dep["router_width"],
        "experts_held": config["num_experts"],
        "expert_offset": dep["expert_offset"],
        "top_k": config["num_experts_per_tok"],
        "eps": float(config["rms_norm_eps"]),
        "seq": config["assumed"]["sequence_length"],
        "per_chip_batch": per_chip_batch}


def samples_per_row(sz: dict) -> int:
    return sz["seq"]


def layer_kind(index: int, interval: int) -> str:
    return "attention" if (index + 1) % interval == 0 else "delta"


def kinds(sz: dict) -> list:
    return [layer_kind(i, sz["interval"]) for i in sz["kept"]]


def attention_layers(sz: dict) -> int:
    return kinds(sz).count("attention")


def delta_layers(sz: dict) -> int:
    return kinds(sz).count("delta")


def expert_layers(sz: dict) -> int:
    return len(sz["kept"])


def _delta_widths(sz: dict):
    return (sz["key_heads"] * sz["key_dim"],
            sz["value_heads"] * sz["value_dim"])


def _mixer_matmul_params(sz: dict, kind: str) -> int:
    d = sz["d"]
    if kind == "delta":
        keys, values = _delta_widths(sz)
        return d * (2 * keys + 2 * values) + d * 2 * sz["value_heads"] \
            + values * d
    h, kv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    return d * h * 2 * hd + 2 * d * kv * hd + h * hd * d


def matmul_params_per_token(sz: dict) -> float:
    """Matmul parameters a token meets in one step: each layer's mixer,
    router, shared expert with its gate and ``top_k x held / experts``
    routed experts by expectation, and the head. The embedding is a
    gather, the convolution counted apart."""
    d = sz["d"]
    routed = sz["top_k"] * sz["experts_held"] / sz["experts"]
    expert_layer = d * sz["experts"] + 3 * d * sz["shared_mlp"] + d \
        + routed * 3 * d * sz["expert_mlp"]
    return sum(_mixer_matmul_params(sz, k) + expert_layer
               for k in kinds(sz)) + d * sz["vocab"]


def param_count(sz: dict) -> int:
    shapes, _ = param_shapes(sz)
    return sum(math.prod(shape) for shape in
               weights.flat_shapes(shapes["params"]).values())


def flops_per_sample(sz: dict) -> float:
    """A token's share of a training step: 6 per matmul parameter it
    meets; attention by the causal half (two products forward, three
    forwards' worth); the rule by its recurrence and the convolution's
    taps likewise."""
    keys, values = _delta_widths(sz)
    attention = 3.0 * attention_layers(sz) * sz["heads"] \
        * 2 * 2 * sz["head_dim"] * (sz["seq"] + 1) / 2
    rule = 3.0 * delta_layers(sz) * gdn_flops.rule_forward_ops(
        1, 1, sz["value_heads"], sz["key_dim"], sz["value_dim"])
    conv = 3.0 * delta_layers(sz) * 2 * sz["conv"] * (2 * keys + values)
    return 6.0 * matmul_params_per_token(sz) + attention + rule + conv


# A zero-centred scale ``w`` starts at 0 in the family; the benchmark's
# seeded draw gives it a deviation of 0.1 (a fan-in of 100), so that
# ``1 + w`` is exercised and stays near one.
ZERO_CENTRED_FAN = 100


def _mixer_shapes(sz: dict, kind: str, at: str):
    d = sz["d"]
    if kind == "delta":
        keys, values = _delta_widths(sz)
        hv = sz["value_heads"]
        return ({"in_proj_qkvz": {"kernel": (d, 2 * keys + 2 * values)},
                 "in_proj_ba": {"kernel": (d, 2 * hv)},
                 "conv": {"kernel": (sz["conv"], 2 * keys + values)},
                 "A_log": (hv,), "dt_bias": (hv,),
                 "norm": {"scale": (sz["value_dim"],)},
                 "out_proj": {"kernel": (values, d)}},
                {f"{at}/in_proj_qkvz/kernel": d, f"{at}/in_proj_ba/kernel": d,
                 f"{at}/conv/kernel": sz["conv"],
                 f"{at}/out_proj/kernel": values})
    h, kv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    return ({"q": {"kernel": (d, h, 2 * hd)}, "k": {"kernel": (d, kv, hd)},
             "v": {"kernel": (d, kv, hd)}, "o": {"kernel": (h, hd, d)},
             "q_norm": {"weight": (hd,)}, "k_norm": {"weight": (hd,)}},
            {f"{at}/q/kernel": d, f"{at}/k/kernel": d, f"{at}/v/kernel": d,
             f"{at}/o/kernel": h * hd,
             f"{at}/q_norm/weight": ZERO_CENTRED_FAN,
             f"{at}/k_norm/weight": ZERO_CENTRED_FAN})


def param_shapes(sz: dict):
    """(``{"params": ..., "aux": {}}`` as nested shapes, fan-ins by
    path)."""
    d, w, ws = sz["d"], sz["expert_mlp"], sz["shared_mlp"]
    held = sz["experts_held"]
    params = {"embed": {"embedding": (sz["vocab"], d)},
              "lm_head": {"kernel": (d, sz["vocab"])},
              "norm_f": {"weight": (d,)}}
    fan = {"params/embed/embedding": d, "params/lm_head/kernel": d,
           "params/norm_f/weight": ZERO_CENTRED_FAN}
    for i, kind in zip(sz["kept"], kinds(sz)):
        at = f"params/layer_{i}"
        mixer, f = _mixer_shapes(sz, kind, f"{at}/mixer")
        fan.update(f)
        fan.update({
            f"{at}/norm1/weight": ZERO_CENTRED_FAN,
            f"{at}/norm2/weight": ZERO_CENTRED_FAN,
            f"{at}/moe/router/kernel": d,
            f"{at}/moe/experts/gate": d, f"{at}/moe/experts/up": d,
            f"{at}/moe/experts/down": w,
            f"{at}/moe/shared/gate/kernel": d,
            f"{at}/moe/shared/up/kernel": d,
            f"{at}/moe/shared/down/kernel": ws,
            f"{at}/moe/shared_gate/kernel": d})
        params[f"layer_{i}"] = {
            "norm1": {"weight": (d,)}, "norm2": {"weight": (d,)},
            "mixer": mixer,
            "moe": {
                "router": {"kernel": (d, sz["experts"])},
                "experts": {"gate": (held, d, w), "up": (held, d, w),
                            "down": (held, w, d)},
                "shared": {"gate": {"kernel": (d, ws)},
                           "up": {"kernel": (d, ws)},
                           "down": {"kernel": (ws, d)}},
                "shared_gate": {"kernel": (d, 1)}}}
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
    from horovod_tpu.models.qwen3next import Qwen3NextConfig, Qwen3NextLM
    return Qwen3NextLM(Qwen3NextConfig(
        vocab_size=sz["vocab"], hidden_size=sz["d"],
        published_layers=sz["published_layers"], kept_layers=sz["kept"],
        full_attention_interval=sz["interval"],
        num_heads=sz["heads"], num_kv_heads=sz["kv_heads"],
        head_dim=sz["head_dim"],
        partial_rotary_factor=sz["rotary"] / sz["head_dim"],
        rope_theta=sz["theta"],
        linear_num_key_heads=sz["key_heads"],
        linear_num_value_heads=sz["value_heads"],
        linear_key_head_dim=sz["key_dim"],
        linear_value_head_dim=sz["value_dim"],
        linear_conv_kernel_dim=sz["conv"],
        a_log_init=sz["a_log_init"], dt_bias_init=sz["dt_bias_init"],
        moe_intermediate_size=sz["expert_mlp"],
        shared_intermediate_size=sz["shared_mlp"],
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
    return train_steps.qwen3next_train_step(model, tx, mesh)


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
    loss_fn = train_steps.qwen3next_loss_fn(model)
    return lambda params, aux, tokens: (loss_fn(params, tokens)[0], aux)


# -- the plain reference --------------------------------------------------

def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def _zero_centred(x, p, eps):
    return _rms(x, eps) * (1.0 + p["weight"])


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
    parameters: ``rule``, ``delta_net``, ``attend``, ``attention``,
    ``routing``, ``expert_layer`` (with ``held``/``offset`` to ask for
    another share, or all the experts), ``block``, ``head_loss``. The
    tests hold the program's modules against them one by one."""
    eps = sz["eps"]
    hk, hv, dk, dv = (sz["key_heads"], sz["value_heads"], sz["key_dim"],
                      sz["value_dim"])
    keys, values = hk * dk, hv * dv

    def rule(q, k, v, g, beta):
        """The recurrence, one position a step: q, k [B, S, Hk, Dk]
        (key head ``h // (Hv / Hk)`` serves value head ``h``); v
        [B, S, Hv, Dv]; g, beta [B, S, Hv]. Checkpoints between chunks
        of ``REFERENCE_CHUNK`` positions."""
        bt, seq = q.shape[:2]
        chunk = REFERENCE_CHUNK if seq % REFERENCE_CHUNK == 0 else seq
        q, k = (jnp.repeat(x, hv // hk, axis=2) for x in (q, k))

        def step(state, xs):
            """``S^T x`` as a product and a sum over the key axis: a
            matrix-vector product a head is no work for the MXU, and
            an elementwise float32 sum takes no matmul precision."""
            qt, kt, vt, gt, bt_ = xs
            state = jnp.exp(gt)[..., None, None] * state
            u = bt_[..., None] * (
                vt - jnp.sum(state * kt[..., :, None], axis=-2))
            state = state + kt[..., :, None] * u[..., None, :]
            return state, jnp.sum(state * qt[..., :, None], axis=-2)

        @jax.checkpoint
        def one_chunk(state, xs):
            return jax.lax.scan(step, state, xs, unroll=4)

        timed = lambda x: jnp.moveaxis(x, 1, 0).reshape(
            seq // chunk, chunk, *x.shape[:1], *x.shape[2:])
        _, out = jax.lax.scan(
            one_chunk, jnp.zeros((bt, hv, dk, dv), jnp.float32),
            tuple(timed(x) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(out.reshape(seq, bt, hv, dv), 0, 1)

    def delta_net(p, x):
        lead = x.shape[:2]
        qkvz = _by_rows(lambda t: t @ p["in_proj_qkvz"]["kernel"], x)
        ba = x @ p["in_proj_ba"]["kernel"]
        seq = x.shape[1]
        mixed = qkvz[..., :2 * keys + values]
        padded = jnp.pad(mixed, ((0, 0), (sz["conv"] - 1, 0), (0, 0)))
        mixed = jax.nn.silu(sum(
            padded[:, j:j + seq] * p["conv"]["kernel"][j]
            for j in range(sz["conv"])))
        l2 = lambda t: t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)
        q = l2(mixed[..., :keys].reshape(*lead, hk, dk)) * dk ** -0.5
        k = l2(mixed[..., keys:2 * keys].reshape(*lead, hk, dk))
        v = mixed[..., 2 * keys:].reshape(*lead, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(p["A_log"] + sz["a_log_init"]) * jax.nn.softplus(
            ba[..., hv:] + p["dt_bias"] + sz["dt_bias_init"])
        o = rule(q, k, v, g, beta)
        z = qkvz[..., 2 * keys + values:].reshape(*lead, hv, dv)
        y = _rms(o, eps) * p["norm"]["scale"] * jax.nn.silu(z)
        return _by_rows(lambda t: t @ p["out_proj"]["kernel"],
                        y.reshape(*lead, values))

    def attend(q, k, v):
        """softmax(q k^T / sqrt(d) + causal) v, dense: q [B, S, H, D];
        k, v [B, S, Hkv, D]; query head h reads key-value head h // (H
        / Hkv). A block of queries at a time."""
        bt, seq, heads, d = q.shape
        group = heads // k.shape[2]
        rows = ROWS_AT_A_TIME if seq % ROWS_AT_A_TIME == 0 else seq
        positions = jnp.arange(seq)

        @jax.checkpoint
        def one(args):
            qi, ki, vi, start = args
            allowed = (start + jnp.arange(rows))[:, None] \
                >= positions[None, :]
            scores = jnp.where(allowed, (qi @ ki.T) / math.sqrt(d), -1e30)
            return jax.nn.softmax(scores, -1) @ vi

        def head(args):
            qh, kh, vh = args
            return jax.lax.map(
                lambda a: one((a[0], kh, vh, a[1])),
                (qh.reshape(seq // rows, rows, d),
                 jnp.arange(0, seq, rows))).reshape(seq, -1)

        flat = lambda t: t.transpose(0, 2, 1, 3).reshape(
            -1, seq, t.shape[-1])
        out = jax.lax.map(head, (
            flat(q), flat(jnp.repeat(k, group, 2)),
            flat(jnp.repeat(v, group, 2))))
        return out.reshape(bt, heads, seq, -1).transpose(0, 2, 1, 3)

    def attention(p, x):
        hd, rot = sz["head_dim"], sz["rotary"]
        q_gate = jnp.einsum("bsd,dhe->bshe", x, p["q"]["kernel"])
        q, gate = q_gate[..., :hd], q_gate[..., hd:]
        k = jnp.einsum("bsd,dhe->bshe", x, p["k"]["kernel"])
        v = jnp.einsum("bsd,dhe->bshe", x, p["v"]["kernel"])
        q = _zero_centred(q, p["q_norm"], eps)
        k = _zero_centred(k, p["k_norm"], eps)
        rotary = lambda t: jnp.concatenate(
            [_rope(t[..., :rot], sz["theta"]), t[..., rot:]], -1)
        out = attend(rotary(q), rotary(k), v) * jax.nn.sigmoid(gate)
        return jnp.einsum("bshe,hed->bsd", out, p["o"]["kernel"])

    def routing(p, x):
        """``(weights [N, experts], chosen [N, top_k])`` of the tokens
        ``x`` [N, d]: a softmax over all the experts, the ``top_k``
        largest, their weights divided by their sum; the weight of an
        expert not chosen is zero."""
        scores = jax.nn.softmax(x @ p["router"]["kernel"], -1)
        _, chosen = jax.lax.top_k(scores, sz["top_k"])
        mask = jnp.sum(jax.nn.one_hot(chosen, sz["experts"]), axis=1)
        picked = scores * mask
        return picked / jnp.sum(picked, -1, keepdims=True), chosen

    def expert_layer(p, x, held=None, offset=None, shared=True):
        """The experts [offset, offset + held) one after another over
        all the tokens, each weighted by its router weight (zero where
        the token did not choose it), plus the shared expert times its
        gate. ``p`` holds ``held`` experts' kernels."""
        held = sz["experts_held"] if held is None else held
        offset = sz["expert_offset"] if offset is None else offset
        xf = x.reshape(-1, x.shape[-1])
        weights_, _ = routing(p, xf)

        @jax.checkpoint
        def one(y, xs):
            gate, up, down, w = xs
            return y + w[:, None] * (
                (jax.nn.silu(xf @ gate) * (xf @ up)) @ down), None

        y = jax.nn.sigmoid(xf @ p["shared_gate"]["kernel"]) \
            * _swiglu(p["shared"], xf) if shared else jnp.zeros_like(xf)
        y, _ = jax.lax.scan(one, y, (
            p["experts"]["gate"], p["experts"]["up"], p["experts"]["down"],
            weights_[:, offset:offset + held].T))
        return y.reshape(x.shape)

    def block(p, index, x, chosen=False):
        """Published layer ``index``; with ``chosen`` what its router
        chose, [tokens, top_k]."""
        h = _zero_centred(x, p["norm1"], eps)
        x = x + (delta_net(p["mixer"], h)
                 if layer_kind(index, sz["interval"]) == "delta"
                 else attention(p["mixer"], h))
        h = _zero_centred(x, p["norm2"], eps)
        if chosen:
            return routing(p["moe"], h.reshape(-1, h.shape[-1]))[1]
        return x + expert_layer(p["moe"], h)

    def head_loss(head, x, targets):
        """Mean cross-entropy of ``x`` [B, T, d] against ``targets``
        [B, T] with the logits ``x head``, a block of rows at a time."""
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
            logp = jax.nn.log_softmax(hidden @ head, -1)
            return jnp.sum(
                jnp.take_along_axis(logp, ids[:, None], -1)[:, 0] * w)

        split = lambda a: a.reshape(-1, rows, *a.shape[1:])
        return -jnp.sum(jax.lax.map(
            rows_ll, (split(flat), split(t), split(live)))) / n

    return {"rule": rule, "delta_net": delta_net, "attend": attend,
            "attention": attention, "routing": routing,
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
            return fns["block"](p, index, x), {}
        return block

    def last(p, x, tokens):
        hidden = _zero_centred(x, p["norm_f"], sz["eps"])
        return fns["head_loss"](p["lm_head"]["kernel"], hidden[:, :-1],
                                tokens[:, 1:])

    return {"first": (("embed",), embed),
            "blocks": [(f"layer_{i}", block_of(i)) for i in sz["kept"]],
            "last": (("norm_f", "lm_head"), last)}
