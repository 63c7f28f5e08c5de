"""Family ``lfm2_moe_lm``: a convolution-attention sparse decoder as
LFM2-24B-A2B (``lfm2_moe``) lays it out: a gated short convolution in
three layers of four, grouped-head attention with normalised q and k in
the fourth, a dense SwiGLU in the leading layers and an expert layer (a
sigmoid router beside a correction bias, no shared expert) in every
later one; trained on next-token cross-entropy through the embedding's
own table.

The program under test is ``horovod_tpu.models.lfm2.Lfm2MoeLM`` with
``train_steps.lfm2_train_step``; this file sizes it from a
configuration file, names its parameter shapes, makes its batch, counts
its FLOPs, and holds its plain float32 reference, which imports nothing
of the program.

What a later builder must know:

* **The kept layers carry their published index** (``kept_layers`` in
  the configuration file): layer ``i``'s operator is what the published
  ``layer_types[i]`` says (attention at 2, 6, ..., the convolution
  elsewhere), its feed-forward the dense SwiGLU where ``i <
  num_dense_layers`` and the expert layer otherwise, and its parameters
  are under ``layer_<i>``.
* **The layer holds a share of the experts** (``num_experts`` in the
  configuration file is how many are held here; the router's width and
  the deployment are under ``deployment``). Program and reference alike
  leave out what the absent experts would add, and there is no shared
  expert: a token that chose no held expert gets zero from the layer.
* **The head is the embedding's table**: the reference's chain hands
  ``(x, table)`` from stage to stage, so that the head's use of the
  table reaches the ``first`` stage's backward (as ``phi4flash_lm``).
* **The convolution is the literal sum of three shifted products**,
  attention the dense masked softmax a block of queries at a time, the
  expert layer a masked dense SwiGLU an expert over a block of tokens at
  a time; position-wise parts run a block of rows at a time under
  ``jax.checkpoint``.
* **FLOPs** count attention by the causal half, the convolution by its
  taps and two gates, and the routed experts by their expectation,
  ``num_experts_per_tok x held / router width`` experts a token (0.5
  here).
* **The readers of this family match kernels by name**
  (``KERNEL_NAMES``); the flash kernels' own readers here
  (``gqa_flash_*``) count k and v by key-value head
  (``chipbench/hybrid_flops.py``).
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

ROWS_AT_A_TIME = 2048     # of the reference's position-wise parts


def sizes(config: dict, per_chip_batch: int) -> dict:
    dep, assumed = config["deployment"], config["assumed"]
    kept = tuple(config["kept_layers"])
    if len(kept) != config["num_hidden_layers"]:
        raise ValueError(f"{len(kept)} kept layers {kept} against "
                         f"num_hidden_layers {config['num_hidden_layers']}")
    if assumed["head_dim"] * config["num_attention_heads"] \
            != config["hidden_size"]:
        raise ValueError("the head is hidden_size / num_attention_heads")
    return {
        "vocab": config["vocab_size"],
        "d": config["hidden_size"],
        "kept": kept,
        "layer_types": tuple(config["layer_types"]),
        "dense_layers": config["num_dense_layers"],
        "mlp": config["intermediate_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": assumed["head_dim"],
        "theta": float(config["rope_parameters"]["rope_theta"]),
        "taps": config["conv_L_cache"],
        "expert_mlp": config["moe_intermediate_size"],
        "experts": dep["router_width"],
        "experts_held": config["num_experts"],
        "expert_offset": dep["expert_offset"],
        "top_k": config["num_experts_per_tok"],
        "scale": float(config["routed_scaling_factor"]),
        "topk_eps": float(assumed["topk_weight_eps"]),
        "eps": float(config["norm_eps"]),
        "seq": assumed["sequence_length"],
        "per_chip_batch": per_chip_batch}


def samples_per_row(sz: dict) -> int:
    return sz["seq"]


def kinds(sz: dict) -> list:
    """``(operator, feed-forward)`` of each kept layer."""
    return [("conv" if sz["layer_types"][i] == "conv" else "attention",
             "dense" if i < sz["dense_layers"] else "experts")
            for i in sz["kept"]]


def attention_layers(sz: dict) -> int:
    return sum(op == "attention" for op, _ in kinds(sz))


def conv_layers(sz: dict) -> int:
    return sum(op == "conv" for op, _ in kinds(sz))


def expert_layers(sz: dict) -> int:
    return sum(ff == "experts" for _, ff in kinds(sz))


def _operator_matmul_params(sz: dict, op: str) -> int:
    d, h, kv, hd = sz["d"], sz["heads"], sz["kv_heads"], sz["head_dim"]
    if op == "conv":
        return 4 * d * d
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def matmul_params_per_token(sz: dict) -> float:
    """Matmul parameters a token meets in one step: each layer's
    operator, the dense SwiGLU or the router and ``top_k x held /
    experts`` routed experts by expectation, and the head (the table a
    second time; the lookup is a gather)."""
    d = sz["d"]
    routed = sz["top_k"] * sz["experts_held"] / sz["experts"]
    ff = {"dense": 3 * d * sz["mlp"],
          "experts": d * sz["experts"] + routed * 3 * d * sz["expert_mlp"]}
    return sum(_operator_matmul_params(sz, op) + ff[kind]
               for op, kind in kinds(sz)) + d * sz["vocab"]


def param_count(sz: dict) -> int:
    shapes, _ = param_shapes(sz)
    return sum(math.prod(shape) for shape in
               weights.flat_shapes(shapes["params"]).values())


def flops_per_sample(sz: dict) -> float:
    """A token's share of a training step: 6 per matmul parameter it
    meets; attention by the causal half (two products forward, three
    forwards' worth); the convolution's taps and its two gates
    likewise."""
    attention = 3.0 * attention_layers(sz) * sz["heads"] \
        * 2 * 2 * sz["head_dim"] * (sz["seq"] + 1) / 2
    conv = 3.0 * conv_layers(sz) * (2 * sz["taps"] + 2) * sz["d"]
    return 6.0 * matmul_params_per_token(sz) + attention + conv


def _operator_shapes(sz: dict, op: str, at: str):
    d, h, kv, hd = sz["d"], sz["heads"], sz["kv_heads"], sz["head_dim"]
    if op == "conv":
        return ({"in_proj": {"kernel": (d, 3 * d)},
                 "conv": {"kernel": (sz["taps"], d)},
                 "out_proj": {"kernel": (d, d)}},
                {f"{at}/in_proj/kernel": d, f"{at}/conv/kernel": sz["taps"],
                 f"{at}/out_proj/kernel": d})
    return ({"q": {"kernel": (d, h, hd)}, "k": {"kernel": (d, kv, hd)},
             "v": {"kernel": (d, kv, hd)}, "o": {"kernel": (h, hd, d)},
             "q_norm": {"scale": (hd,)}, "k_norm": {"scale": (hd,)}},
            {f"{at}/q/kernel": d, f"{at}/k/kernel": d, f"{at}/v/kernel": d,
             f"{at}/o/kernel": h * hd})


def param_shapes(sz: dict):
    """(``{"params": ..., "aux": {}}`` as nested shapes, fan-ins by
    path). Norm scales are ones and the router's bias zeros
    (``weights.leaf_value``), as the family starts them."""
    d, w, held = sz["d"], sz["expert_mlp"], sz["experts_held"]
    params = {"embed": {"embedding": (sz["vocab"], d)},
              "norm_f": {"scale": (d,)}}
    fan = {"params/embed/embedding": d}
    for i, (op, ff) in zip(sz["kept"], kinds(sz)):
        at = f"params/layer_{i}"
        operator, f = _operator_shapes(sz, op, f"{at}/operator")
        fan.update(f)
        layer = {"operator_norm": {"scale": (d,)},
                 "ffn_norm": {"scale": (d,)}, "operator": operator}
        if ff == "dense":
            layer["mlp"] = {"gate": {"kernel": (d, sz["mlp"])},
                            "up": {"kernel": (d, sz["mlp"])},
                            "down": {"kernel": (sz["mlp"], d)}}
            fan.update({f"{at}/mlp/gate/kernel": d, f"{at}/mlp/up/kernel": d,
                        f"{at}/mlp/down/kernel": sz["mlp"]})
        else:
            layer["moe"] = {
                "router": {"kernel": (d, sz["experts"]),
                           "bias": (sz["experts"],)},
                "experts": {"gate": (held, d, w), "up": (held, d, w),
                            "down": (held, w, d)}}
            fan.update({f"{at}/moe/router/kernel": d,
                        f"{at}/moe/experts/gate": d,
                        f"{at}/moe/experts/up": d,
                        f"{at}/moe/experts/down": w})
        params[f"layer_{i}"] = layer
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
    from horovod_tpu.models.lfm2 import Lfm2MoeConfig, Lfm2MoeLM
    return Lfm2MoeLM(Lfm2MoeConfig(
        vocab_size=sz["vocab"], hidden_size=sz["d"],
        layer_types=sz["layer_types"], kept_layers=sz["kept"],
        num_dense_layers=sz["dense_layers"], intermediate_size=sz["mlp"],
        num_heads=sz["heads"], num_kv_heads=sz["kv_heads"],
        rope_theta=sz["theta"], conv_L_cache=sz["taps"],
        moe_intermediate_size=sz["expert_mlp"],
        n_routed_experts=sz["experts"], num_experts_per_tok=sz["top_k"],
        routed_scaling_factor=sz["scale"], topk_weight_eps=sz["topk_eps"],
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
    return train_steps.lfm2_train_step(model, tx, mesh)


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
    loss_fn = train_steps.lfm2_loss_fn(model)
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
    parameters: ``short_conv``, ``attend``, ``attention``, ``routing``,
    ``expert_layer`` (with ``held``/``offset`` to ask for another share,
    or all the experts), ``mlp``, ``block``, ``head_loss``. The tests
    hold the program's modules against them one by one."""
    eps, d = sz["eps"], sz["d"]

    def short_conv(p, x):
        """``[B, C, u] = x W_in``; the three taps of ``B u`` as three
        shifted products; ``(C c) W_out``. No activation function."""
        seq = x.shape[1]
        bcu = _by_rows(lambda t: t @ p["in_proj"]["kernel"], x)
        b, c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
        padded = jnp.pad(b * u, ((0, 0), (sz["taps"] - 1, 0), (0, 0)))
        conv = sum(padded[:, j:j + seq] * p["conv"]["kernel"][j]
                   for j in range(sz["taps"]))
        return _by_rows(lambda t: t @ p["out_proj"]["kernel"], c * conv)

    def attend(q, k, v):
        """softmax(q k^T / sqrt(d) + causal) v, dense: q [B, S, H, D];
        k, v [B, S, Hkv, D]; query head h reads key-value head h // (H
        / Hkv). A block of queries at a time."""
        bt, seq, heads, hd = q.shape
        group = heads // k.shape[2]
        rows = ROWS_AT_A_TIME if seq % ROWS_AT_A_TIME == 0 else seq
        positions = jnp.arange(seq)

        @jax.checkpoint
        def one(args):
            qi, ki, vi, start = args
            allowed = (start + jnp.arange(rows))[:, None] \
                >= positions[None, :]
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

    def attention(p, x):
        q = jnp.einsum("bsd,dhe->bshe", x, p["q"]["kernel"])
        k = jnp.einsum("bsd,dhe->bshe", x, p["k"]["kernel"])
        v = jnp.einsum("bsd,dhe->bshe", x, p["v"]["kernel"])
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
        out = attend(_rope(q, sz["theta"]), _rope(k, sz["theta"]), v)
        return jnp.einsum("bshe,hed->bsd", out, p["o"]["kernel"])

    def routing(p, x):
        """``(weights [N, experts], chosen [N, top_k])`` of the tokens
        ``x`` [N, d]: sigmoid scores, the ``top_k`` largest of ``score +
        bias``, the chosen scores divided by (their sum + ``topk_eps``)
        and scaled; the weight of an expert not chosen is zero."""
        scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
        _, chosen = jax.lax.top_k(scores + p["router"]["bias"], sz["top_k"])
        picked = scores * jnp.sum(
            jax.nn.one_hot(chosen, sz["experts"]), axis=1)
        return sz["scale"] * picked / (
            jnp.sum(picked, -1, keepdims=True) + sz["topk_eps"]), chosen

    def expert_layer(p, x, held=None, offset=None):
        """The experts [offset, offset + held) one after another, each
        weighted by its router weight (zero where the token did not
        choose it), over a block of tokens at a time. No shared expert.
        ``p`` holds ``held`` experts' kernels."""
        held = sz["experts_held"] if held is None else held
        offset = sz["expert_offset"] if offset is None else offset
        xf = x.reshape(-1, d)
        share = routing(p, xf)[0][:, offset:offset + held]
        experts = (p["experts"]["gate"], p["experts"]["up"],
                   p["experts"]["down"])

        def rows(t):
            xs, ws = t[:, :d], t[:, d:]

            def one(y, e):
                gate, up, down, w = e
                return y + w[:, None] * (
                    (jax.nn.silu(xs @ gate) * (xs @ up)) @ down), None

            return jax.lax.scan(one, jnp.zeros_like(xs),
                                (*experts, ws.T))[0]

        return _by_rows(rows, jnp.concatenate([xf, share], -1)) \
            .reshape(x.shape)

    def mlp(p, x):
        return _by_rows(
            lambda t: (jax.nn.silu(t @ p["gate"]["kernel"])
                       * (t @ p["up"]["kernel"])) @ p["down"]["kernel"], x)

    def block(p, index, x, chosen=False):
        """Published layer ``index``; with ``chosen`` what its router
        chose, [tokens, top_k]."""
        h = _rms(x, p["operator_norm"], eps)
        x = x + (short_conv(p["operator"], h)
                 if sz["layer_types"][index] == "conv"
                 else attention(p["operator"], h))
        h = _rms(x, p["ffn_norm"], eps)
        if index < sz["dense_layers"]:
            return x + mlp(p["mlp"], h)
        if chosen:
            return routing(p["moe"], h.reshape(-1, d))[1]
        return x + expert_layer(p["moe"], h)

    def head_loss(table, x, targets):
        """Mean cross-entropy of ``x`` [B, T, d] against ``targets``
        [B, T] with the logits ``x table^T``, a block of rows at a
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
            logp = jax.nn.log_softmax(hidden @ table.T, -1)
            return jnp.sum(
                jnp.take_along_axis(logp, ids[:, None], -1)[:, 0] * w)

        split = lambda a: a.reshape(-1, rows, *a.shape[1:])
        return -jnp.sum(jax.lax.map(
            rows_ll, (split(flat), split(t), split(live)))) / n

    return {"short_conv": short_conv, "attend": attend,
            "attention": attention, "routing": routing,
            "expert_layer": expert_layer, "mlp": mlp, "block": block,
            "head_loss": head_loss}


def reference_stages(sz: dict) -> dict:
    """The plain model as stages for ``check.StagedGradient``. The
    activation handed along the chain is ``(x, table)`` (module
    docstring)."""
    fns = reference_fns(sz)

    def embed(p, aux, tokens):
        table = p["embed"]["embedding"]
        return (table[tokens], table), {}

    def block_of(index):
        def block(p, aux, carried):
            """A row of the batch at a time, recomputed in the backward
            pass: rows meet nowhere before the loss, and four rows of
            8,192 at once leave a block's backward 5 GB of temporaries
            beside 10 GB of parameters, momentum and gradients."""
            x, table = carried
            one_row = jax.checkpoint(
                lambda row: fns["block"](p, index, row[None])[0])
            return (jax.lax.map(one_row, x), table), {}
        return block

    def last(p, carried, tokens):
        x, table = carried
        hidden = _rms(x, p["norm_f"], sz["eps"])
        return fns["head_loss"](table, hidden[:, :-1], tokens[:, 1:])

    return {"first": (("embed",), embed),
            "blocks": [(f"layer_{i}", block_of(i)) for i in sz["kept"]],
            "last": (("norm_f",), last)}
