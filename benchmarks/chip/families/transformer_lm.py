"""Family ``transformer_lm``: a decoder-only language model trained on
next-token cross-entropy.

The program under test is ``horovod_tpu.models.transformer.TransformerLM``
with the step of ``horovod_tpu.models.train_steps``; this file only
sizes it from a configuration file, names its parameter shapes, makes
its batch, and holds its plain float32 reference, which imports
nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import flops

SAMPLE = "tokens"
LN_EPS = 1e-6   # flax LayerNorm's default, which the program uses


def sizes(config: dict, per_chip_batch: int) -> dict:
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    if config["intermediate_size"] % d:
        raise ValueError("intermediate_size must be a multiple of "
                         "hidden_size: TransformerLM takes a whole ratio")
    return {"vocab": config["vocab_size"], "layers": config["num_hidden_layers"],
            "d": d, "heads": heads, "head_dim": d // heads,
            "mlp": config["intermediate_size"],
            "seq": config["assumed"]["sequence_length"],
            "theta": float(config.get("rotary_emb_base", 10000)),
            "per_chip_batch": per_chip_batch}


def samples_per_row(sz: dict) -> int:
    return sz["seq"]


def flops_per_sample(sz: dict) -> float:
    return flops.lm_flops_per_token(sz["vocab"], sz["layers"], sz["d"],
                                    sz["mlp"], sz["seq"])


def param_shapes(sz: dict):
    """(``{"params": ..., "aux": {}}`` as nested shapes, fan-ins by
    path). The q, k, v kernels are [d, heads, head_dim]: their fan-in
    is d, not the product of the leading axes."""
    d, h, hd, mlp = sz["d"], sz["heads"], sz["head_dim"], sz["mlp"]
    params = {"embed": {"embedding": (sz["vocab"], d)},
              "lm_head": {"kernel": (d, sz["vocab"])},
              "ln_f": {"scale": (d,)}}
    fan = {"params/embed/embedding": d, "params/lm_head/kernel": d}
    for i in range(sz["layers"]):
        b = f"block_{i}"
        params[b] = {
            "attn": {"q": {"kernel": (d, h, hd)}, "k": {"kernel": (d, h, hd)},
                     "v": {"kernel": (d, h, hd)}, "o": {"kernel": (h, hd, d)}},
            "ln1": {"scale": (d,)}, "ln2": {"scale": (d,)},
            "mlp": {"up": {"kernel": (d, mlp)}, "down": {"kernel": (mlp, d)}}}
        for n in "qkvo":
            fan[f"params/{b}/attn/{n}/kernel"] = d
        fan[f"params/{b}/mlp/up/kernel"] = d
        fan[f"params/{b}/mlp/down/kernel"] = mlp
    return {"params": params, "aux": {}}, fan


def make_batch(sz: dict, rows: int):
    """``key -> (tokens,)``: ``rows`` sequences of random tokens, every
    row different."""
    def batch(key):
        return (jax.random.randint(key, (rows, sz["seq"]), 0, sz["vocab"],
                                   jnp.int32),)
    return batch


# -- the program ----------------------------------------------------------

def build_model(sz: dict):
    from horovod_tpu.models.transformer import TransformerConfig, TransformerLM
    return TransformerLM(TransformerConfig(
        vocab_size=sz["vocab"], num_layers=sz["layers"],
        num_heads=sz["heads"], head_dim=sz["head_dim"],
        mlp_ratio=sz["mlp"] // sz["d"], max_seq_len=sz["seq"],
        dtype=jnp.bfloat16, rope_theta=sz["theta"]))


def program_shapes(model, sz: dict):
    """The program's own parameter tree as shapes, to be held against
    :func:`param_shapes`."""
    tree = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, sz["seq"]), jnp.int32)),
        jax.random.key(0))
    return {"params": tree["params"], "aux": {}}


def injit_step(model, tx, mesh):
    from horovod_tpu.models import train_steps
    return train_steps.lm_train_step(model, tx, mesh)


def injit_args(state: dict, batch: tuple) -> tuple:
    return (state["params"], state["opt"], *batch)


def injit_unpack(out, state: dict):
    params, opt, loss = out
    return {"params": params, "aux": state["aux"], "opt": opt}, loss


def program_loss(model):
    """``(params, aux, tokens) -> (loss, aux)`` through the program's
    model and chunked loss: the eager cells' backward."""
    from horovod_tpu.models import train_steps
    loss_fn = train_steps.lm_loss_fn(model)
    return lambda params, aux, tokens: (loss_fn(params, tokens), aux)


# -- the plain reference --------------------------------------------------

def _layernorm(x, scale):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale


def _rope(x, theta):
    """x: [S, H, D], rotary over the whole head, halves paired."""
    s, _, d = x.shape
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def reference_stages(sz: dict) -> dict:
    """The model in float32 with dense softmax attention, as stages
    for ``check.StagedGradient``: sequential pre-norm blocks, tanh
    GELU, rotary on the whole head, no biases, an untied float32 head.
    Rows go one at a time through attention and the head."""
    theta, hd = sz["theta"], sz["head_dim"]

    @jax.checkpoint
    def attend_row(qkv):
        q, k, v = qkv                                      # [S, H, D]
        q, k = _rope(q, theta), _rope(k, theta)
        logits = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        s = q.shape[0]
        mask = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(mask[None], logits, -1e30), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    def embed(p, aux, tokens):
        return p["embed"]["embedding"][tokens], {}

    def block(p, aux, x):
        h = _layernorm(x, p["ln1"]["scale"])
        q = jnp.einsum("bsd,dhe->bshe", h, p["attn"]["q"]["kernel"])
        k = jnp.einsum("bsd,dhe->bshe", h, p["attn"]["k"]["kernel"])
        v = jnp.einsum("bsd,dhe->bshe", h, p["attn"]["v"]["kernel"])
        a = jax.lax.map(attend_row, (q, k, v))
        x = x + jnp.einsum("bshe,hed->bsd", a, p["attn"]["o"]["kernel"])
        h = _layernorm(x, p["ln2"]["scale"])
        h = jax.nn.gelu(h @ p["mlp"]["up"]["kernel"], approximate=True)
        return x + h @ p["mlp"]["down"]["kernel"], {}

    def head_loss(p, x, tokens):
        x = _layernorm(x, p["ln_f"]["scale"])
        head = p["lm_head"]["kernel"]

        @jax.checkpoint
        def row_ll(xs):
            hidden, targets = xs
            logp = jax.nn.log_softmax(hidden @ head, -1)
            return jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

        total = jnp.sum(jax.lax.map(row_ll, (x[:, :-1], tokens[:, 1:])))
        return -total / (tokens.shape[0] * (tokens.shape[1] - 1))

    return {"first": (("embed",), embed),
            "blocks": [(f"block_{i}", block) for i in range(sz["layers"])],
            "last": (("ln_f", "lm_head"), head_loss)}
