"""Family ``olmo_hybrid_lm``: a dense hybrid decoder as Olmo-Hybrid-7B
(``olmo_hybrid``) lays it out: Gated DeltaNet with key heads of 96 over
value heads of 192 and ``beta`` in (0, 2) in three layers of four, full
softmax attention with no positional signal in the fourth, a dense
SwiGLU in every one, the block's norms on the **outputs** of its two
sub-layers; trained on next-token cross-entropy through an untied head.

The program under test is
``horovod_tpu.models.olmo_hybrid.OlmoHybridLM`` with
``train_steps.olmo_hybrid_train_step``; this file sizes it from a
configuration file, names its parameter shapes, makes its batch, counts
its FLOPs, and holds its plain float32 reference, which imports nothing
of the program.

What a later builder must know:

* **The kept layers carry their published index** (``kept_layers`` in
  the configuration file): layer ``i`` is what ``layer_types[i]`` of
  the published 32 says, and its parameters are under ``layer_<i>``.
* **The rule is a literal ``lax.scan`` over time** (one matrix state
  [96, 192] a head, one position a step), nested by chunk under
  ``jax.checkpoint`` so that its backward fits, and a linear layer's
  mixer runs a group of five heads at a time (``HEAD_GROUPS``);
  attention is the dense
  softmax, a block of queries at a time; position-wise parts run a
  block of rows at a time under ``jax.checkpoint``. A block's backward
  fits beside 11.1 GB of float32 parameters, momentum and gradients
  (``tests/chip_bench/test_olmohybrid_cell.py`` says how that was
  read).
* **FLOPs** count attention by the causal half and the rule by its
  recurrence at the published 96 x 192 (``chipbench/gdn_flops.py``: 7
  operations a state entry and position, whatever the kernels lay out
  and execute).
* **The readers of this family match kernels by name**
  (``KERNEL_NAMES``); ``mla_flash_*`` read ``sz["heads"]`` heads of
  ``sz["head_dim"]`` over ``attention_layers``, a key and a value head
  a query head, which is this model's full attention exactly.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import gdn_flops, weights

SAMPLE = "tokens"

# How the device's ops line names the kernels (trace_reduce.short_name).
KERNEL_NAMES = {
    "flash": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
    "gdn": ("gdn_fwd", "gdn_bwd"),
}

ROWS_AT_A_TIME = 2048     # of the reference's position-wise parts
REFERENCE_CHUNK = 128     # of its recurrence: checkpoints between chunks
HEAD_GROUPS = 6           # of a linear layer's heads: a group at a time


def sizes(config: dict, per_chip_batch: int) -> dict:
    gates = config["assumed"]["gates"]
    kept = tuple(config["kept_layers"])
    if len(kept) != config["num_hidden_layers"]:
        raise ValueError(f"{len(kept)} kept layers {kept} against "
                         f"num_hidden_layers {config['num_hidden_layers']}")
    if len(config["layer_types"]) != config["published"]["num_hidden_layers"]:
        raise ValueError("layer_types is the published list, a kind a "
                         "published layer")
    heads = config["num_attention_heads"]
    return {
        "vocab": config["vocab_size"],
        "d": config["hidden_size"],
        "kept": kept,
        "layer_types": tuple(config["layer_types"]),
        "heads": heads,
        "head_dim": config["assumed"]["head_dim"],
        "key_heads": config["linear_num_key_heads"],
        "value_heads": config["linear_num_value_heads"],
        "key_dim": config["linear_key_head_dim"],
        "value_dim": config["linear_value_head_dim"],
        "conv": config["linear_conv_kernel_dim"],
        "neg_eigval": bool(config["linear_allow_neg_eigval"]),
        "a_log_init": float(gates["a_log_init"]),
        "dt_bias_init": float(gates["dt_bias_init"]),
        "mlp": config["intermediate_size"],
        "eps": float(config["rms_norm_eps"]),
        "seq": config["assumed"]["sequence_length"],
        "per_chip_batch": per_chip_batch}


def samples_per_row(sz: dict) -> int:
    return sz["seq"]


def kinds(sz: dict) -> list:
    """``delta`` or ``attention`` a kept layer."""
    return ["delta" if sz["layer_types"][i] == "linear_attention"
            else "attention" for i in sz["kept"]]


def attention_layers(sz: dict) -> int:
    return kinds(sz).count("attention")


def delta_layers(sz: dict) -> int:
    return kinds(sz).count("delta")


def _delta_widths(sz: dict):
    return (sz["key_heads"] * sz["key_dim"],
            sz["value_heads"] * sz["value_dim"])


def _mixer_matmul_params(sz: dict, kind: str) -> int:
    d = sz["d"]
    if kind == "delta":
        keys, values = _delta_widths(sz)
        return d * (2 * keys + 2 * values) + d * 2 * sz["value_heads"] \
            + values * d
    return 4 * d * d


def matmul_params_per_token(sz: dict) -> float:
    """Matmul parameters a token meets in one step: each layer's mixer
    and SwiGLU, and the head. The embedding is a gather, the
    convolution counted apart."""
    d = sz["d"]
    return sum(_mixer_matmul_params(sz, k) + 3 * d * sz["mlp"]
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
    return ({"q": {"kernel": (d, d)}, "k": {"kernel": (d, d)},
             "v": {"kernel": (d, d)}, "o": {"kernel": (d, d)},
             "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)}},
            {f"{at}/{m}/kernel": d for m in "qkvo"})


def param_shapes(sz: dict):
    """(``{"params": ..., "aux": {}}`` as nested shapes, fan-ins by
    path). Every norm's scale starts at one, as published."""
    d, w = sz["d"], sz["mlp"]
    params = {"embed": {"embedding": (sz["vocab"], d)},
              "lm_head": {"kernel": (d, sz["vocab"])},
              "norm_f": {"scale": (d,)}}
    fan = {"params/embed/embedding": d, "params/lm_head/kernel": d}
    for i, kind in zip(sz["kept"], kinds(sz)):
        at = f"params/layer_{i}"
        mixer, f = _mixer_shapes(sz, kind, f"{at}/mixer")
        fan.update(f)
        fan.update({f"{at}/mlp/gate/kernel": d, f"{at}/mlp/up/kernel": d,
                    f"{at}/mlp/down/kernel": w})
        params[f"layer_{i}"] = {
            "mixer": mixer,
            "mixer_norm": {"scale": (d,)}, "mlp_norm": {"scale": (d,)},
            "mlp": {"gate": {"kernel": (d, w)}, "up": {"kernel": (d, w)},
                    "down": {"kernel": (w, d)}}}
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
    from horovod_tpu.models.olmo_hybrid import OlmoHybridConfig, OlmoHybridLM
    return OlmoHybridLM(OlmoHybridConfig(
        vocab_size=sz["vocab"], hidden_size=sz["d"],
        layer_types=sz["layer_types"], kept_layers=sz["kept"],
        intermediate_size=sz["mlp"], num_heads=sz["heads"],
        linear_num_key_heads=sz["key_heads"],
        linear_num_value_heads=sz["value_heads"],
        linear_key_head_dim=sz["key_dim"],
        linear_value_head_dim=sz["value_dim"],
        linear_conv_kernel_dim=sz["conv"],
        linear_allow_neg_eigval=sz["neg_eigval"],
        a_log_init=sz["a_log_init"], dt_bias_init=sz["dt_bias_init"],
        rms_norm_eps=sz["eps"], dtype=jnp.bfloat16))


def program_shapes(model, sz: dict):
    tree = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, sz["seq"]), jnp.int32)),
        jax.random.key(0))
    return {"params": tree["params"], "aux": {}}


def injit_step(model, tx, mesh):
    from horovod_tpu.models import train_steps
    return train_steps.olmo_hybrid_train_step(model, tx, mesh)


def injit_args(state: dict, batch: tuple) -> tuple:
    return (state["params"], state["opt"], *batch)


def injit_unpack(out, state: dict):
    params, opt, loss = out
    return {"params": params, "aux": state["aux"], "opt": opt}, loss


def program_loss(model):
    from horovod_tpu.models import train_steps
    loss_fn = train_steps.olmo_hybrid_loss_fn(model)
    return lambda params, aux, tokens: (loss_fn(params, tokens), aux)


# -- the plain reference --------------------------------------------------

def _rms(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * p["scale"]


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
    ``mlp``, ``block``, ``head_loss``. The tests hold the program's
    modules against them one by one."""
    eps = sz["eps"]
    hk, hv, dk, dv = (sz["key_heads"], sz["value_heads"], sz["key_dim"],
                      sz["value_dim"])
    keys, values = hk * dk, hv * dv
    beta_max = 2.0 if sz["neg_eigval"] else 1.0

    def rule(q, k, v, g, beta):
        """The recurrence, one position a step: q, k [B, S, Hk, Dk]
        (key head ``h // (Hv / Hk)`` serves value head ``h``); v
        [B, S, Hv, Dv]; g, beta [B, S, Hv]. Checkpoints between chunks
        of ``REFERENCE_CHUNK`` positions."""
        bt, seq = q.shape[:2]
        chunk = REFERENCE_CHUNK if seq % REFERENCE_CHUNK == 0 else seq
        heads = v.shape[2]
        q, k = (jnp.repeat(x, heads // x.shape[2], axis=2) for x in (q, k))

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
            one_chunk, jnp.zeros((bt, heads, dk, dv), jnp.float32),
            tuple(timed(x) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(out.reshape(seq, bt, heads, dv), 0, 1)

    def delta_net(p, x):
        """A group of heads at a time (``HEAD_GROUPS``; a head's path
        from ``x`` to its rows of ``W_o`` meets no other head's), each
        group recomputed in the backward pass: over all 30 heads a
        block's backward is 4.5 GB of temporaries, which do not fit
        beside 11.1 GB of float32 state."""
        lead, seq = x.shape[:2], x.shape[1]
        groups = math.gcd(HEAD_GROUPS, hk)
        nk, nv = hk // groups, hv // groups

        def split(w, width):
            """[groups, rows, columns a group] of ``w`` [rows, heads x
            ``width``]."""
            return w.reshape(w.shape[0], groups, -1, width).transpose(
                1, 0, 2, 3).reshape(groups, w.shape[0], -1)

        def parts(w, last_width):
            """q's, k's, v's and, where ``w`` has them, z's columns."""
            cuts = (0, keys, 2 * keys, 2 * keys + values, w.shape[1])
            widths = (dk, dk, dv, last_width)
            return tuple(split(w[:, a:b], n) for a, b, n in
                         zip(cuts, cuts[1:], widths) if b > a)

        l2 = lambda t: t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)

        @jax.checkpoint
        def one(out, ws):
            (wq, wk, wv, wz), (cq, ck, cv), wb, wa, a_log, dt_bias, wo = ws

            def mixed(w, taps):
                padded = jnp.pad(x @ w, ((0, 0), (sz["conv"] - 1, 0), (0, 0)))
                return jax.nn.silu(sum(
                    padded[:, j:j + seq] * taps[j] for j in range(sz["conv"])))

            q = l2(mixed(wq, cq).reshape(*lead, nk, dk)) * dk ** -0.5
            k = l2(mixed(wk, ck).reshape(*lead, nk, dk))
            v = mixed(wv, cv).reshape(*lead, nv, dv)
            beta = beta_max * jax.nn.sigmoid(x @ wb)
            g = -jnp.exp(a_log + sz["a_log_init"]) * jax.nn.softplus(
                x @ wa + dt_bias + sz["dt_bias_init"])
            o = rule(q, k, v, g, beta)
            y = _rms(o, p["norm"], eps) * jax.nn.silu(
                (x @ wz).reshape(*lead, nv, dv))
            return out + y.reshape(*lead, nv * dv) @ wo, None

        ba = p["in_proj_ba"]["kernel"]
        out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
            parts(p["in_proj_qkvz"]["kernel"], dv),
            parts(p["conv"]["kernel"], dv),
            split(ba[:, :hv], 1), split(ba[:, hv:], 1),
            p["A_log"].reshape(groups, nv), p["dt_bias"].reshape(groups, nv),
            p["out_proj"]["kernel"].reshape(groups, nv * dv, -1)))
        return out

    def attend(q, k, v):
        """softmax(q k^T / sqrt(d) + causal) v, dense: q, k, v
        [B, S, H, D]. A block of queries at a time."""
        bt, seq, heads, d = q.shape
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
        out = jax.lax.map(head, (flat(q), flat(k), flat(v)))
        return out.reshape(bt, heads, seq, -1).transpose(0, 2, 1, 3)

    def attention(p, x):
        """No rotary, no gate: the norms are over the whole projection,
        before it is split into heads."""
        heads = (*x.shape[:2], sz["heads"], sz["head_dim"])
        proj = lambda m: _by_rows(lambda t: t @ p[m]["kernel"], x)
        q = _rms(proj("q"), p["q_norm"], eps).reshape(heads)
        k = _rms(proj("k"), p["k_norm"], eps).reshape(heads)
        out = attend(q, k, proj("v").reshape(heads))
        return _by_rows(lambda t: t @ p["o"]["kernel"], out.reshape(x.shape))

    def mlp(p, x):
        return _by_rows(
            lambda t: (jax.nn.silu(t @ p["gate"]["kernel"])
                       * (t @ p["up"]["kernel"])) @ p["down"]["kernel"], x)

    def block(p, index, x):
        """Published layer ``index``: the norms on the outputs."""
        mixer = delta_net if sz["layer_types"][index] == "linear_attention" \
            else attention
        x = x + _rms(mixer(p["mixer"], x), p["mixer_norm"], eps)
        return x + _rms(mlp(p["mlp"], x), p["mlp_norm"], eps)

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
            "attention": attention, "mlp": mlp, "block": block,
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
        hidden = _rms(x, p["norm_f"], sz["eps"])
        return fns["head_loss"](p["lm_head"]["kernel"], hidden[:, :-1],
                                tokens[:, 1:])

    return {"first": (("embed",), embed),
            "blocks": [(f"layer_{i}", block_of(i)) for i in sz["kept"]],
            "last": (("norm_f", "lm_head"), last)}
