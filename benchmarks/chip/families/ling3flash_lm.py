"""Family ``ling3flash_lm``: a hybrid linear-attention sparse decoder as
Ling-3.0-flash (``bailing_hybrid``) lays it out: Kimi delta attention
(a delta rule with a decay for every key channel) in five layers of
six, latent attention in the sixth, a dense SwiGLU in the leading
layers and an expert layer (a sigmoid router beside a correction bias
whose choice is limited to groups, a shared expert) in every later one;
trained on next-token cross-entropy through an untied head.

The program under test is ``horovod_tpu.models.ling3flash.Ling3FlashLM``
with ``train_steps.ling3flash_train_step``; this file sizes it from a
configuration file, names its parameter shapes, makes its batch, counts
its FLOPs, and holds its plain float32 reference, which imports nothing
of the program.

What a later builder must know:

* **The kept layers carry their published index** (``kept_layers`` in
  the configuration file): layer ``i`` is latent attention where ``(i +
  1) mod layer_group_size = 0`` and Kimi delta attention otherwise, its
  feed-forward the dense SwiGLU where ``i < first_k_dense_replace`` and
  the expert layer otherwise, and its parameters are under
  ``layer_<i>``.
* **The layer holds a share of the experts** (``num_experts`` in the
  configuration file is how many are held here; the router's width and
  the deployment are under ``deployment``). Program and reference alike
  route over all of them, groups and all, and leave out what the absent
  experts would add; the shared expert is whole.
* **The rule is a literal ``lax.scan`` over time** (one matrix state a
  head, one position a step, the decay a vector over the state's rows),
  nested by chunk under ``jax.checkpoint`` so that its backward fits;
  attention is the dense softmax, a block of queries at a time; the
  group-limited choice is written out with a sort; the expert layer is
  a masked dense SwiGLU an expert over a block of tokens at a time.
* **A mixer runs a group of heads at a time** (``HEAD_GROUPS``), from
  its projections to the group's rows of ``W_o``, each group recomputed
  in the backward pass: over all 32 heads at once a Kimi delta
  attention block's backward compiles to 11.2 GB of temporaries for a
  described v5e and the latent block's to some 6, beside 9.9 GB of
  parameters, momentum and gradients (PR 41's first chip call failed
  so); by groups they are 3.1 and 2.8. Compile ``reference_stages``'
  blocks for the described chip before changing them (``PERF.md``).
* **Where the gates start** is a pair of constants beside the learnt
  leaves (``assumed.gates``), in program and reference alike.
* **FLOPs** count attention by the causal half at its two head sizes,
  the rule by its recurrence (``chipbench/kda_flops.py``) and the routed
  experts by their expectation, ``num_experts_per_tok x held / router
  width`` experts a token (0.125 here).
* **The readers of this family match kernels by name**
  (``KERNEL_NAMES``); the flash kernels' own readers here
  (``latent_flash_*``) count q and k at the score head's size and v and
  o at the value head's (``chipbench/hybrid_flops.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import kda_flops, weights

SAMPLE = "tokens"

# How the device's ops line names the kernels (trace_reduce.short_name):
# the Pallas kernels by their ``name=``, the grouped products by the
# instruction the TPU compiler lowers ``jax.lax.ragged_dot`` to.
KERNEL_NAMES = {
    "flash": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
    "grouped": ("ragged-dot",),
    "kda": ("kda_fwd", "kda_bwd"),
}

ROWS_AT_A_TIME = 2048     # of the reference's position-wise parts
REFERENCE_CHUNK = 128     # of its recurrence: checkpoints between chunks
HEAD_GROUPS = 4           # of a mixer's heads: a group at a time


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
        "group_size": config["layer_group_size"],
        "dense_layers": config["first_k_dense_replace"],
        "mlp": config["intermediate_size"],
        "heads": config["num_attention_heads"],
        "kda_dim": config["head_dim"],
        "conv": config["short_conv_kernel_size"],
        "lower": float(config["kda_lower_bound"]),
        "a_log_init": float(gates["a_log_init"]),
        "dt_bias_init": float(gates["dt_bias_init"]),
        "kv_rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "theta": float(config["rope_theta"]),
        "expert_mlp": config["moe_intermediate_size"],
        "shared_mlp": config["moe_shared_expert_intermediate_size"],
        "experts": dep["router_width"],
        "experts_held": config["num_experts"],
        "expert_offset": dep["expert_offset"],
        "top_k": config["num_experts_per_tok"],
        "groups": config["n_group"],
        "top_groups": config["topk_group"],
        "scale": float(config["routed_scaling_factor"]),
        "row_tier_headroom": float(config["assumed"]["row_tier_headroom"]),
        "eps": float(config["rms_norm_eps"]),
        "seq": config["assumed"]["sequence_length"],
        "per_chip_batch": per_chip_batch}


def samples_per_row(sz: dict) -> int:
    return sz["seq"]


def layer_kind(index: int, group_size: int) -> str:
    return "attention" if (index + 1) % group_size == 0 else "kda"


def kinds(sz: dict) -> list:
    """``(mixer, feed-forward)`` of each kept layer."""
    return [(layer_kind(i, sz["group_size"]),
             "dense" if i < sz["dense_layers"] else "experts")
            for i in sz["kept"]]


def attention_layers(sz: dict) -> int:
    return sum(mixer == "attention" for mixer, _ in kinds(sz))


def kda_layers(sz: dict) -> int:
    return sum(mixer == "kda" for mixer, _ in kinds(sz))


def expert_layers(sz: dict) -> int:
    return sum(ff == "experts" for _, ff in kinds(sz))


def _mixer_matmul_params(sz: dict, kind: str) -> int:
    d, h = sz["d"], sz["heads"]
    if kind == "kda":
        width = h * sz["kda_dim"]
        return d * 4 * width + d * 2 * h + width * d
    return d * h * (sz["nope"] + sz["rope"]) + d * (sz["kv_rank"] + sz["rope"]) \
        + sz["kv_rank"] * h * (sz["nope"] + sz["v_dim"]) + d * h \
        + h * sz["v_dim"] * d


def matmul_params_per_token(sz: dict) -> float:
    """Matmul parameters a token meets in one step: each layer's mixer,
    the dense SwiGLU or the router, the shared expert and ``top_k x
    held / experts`` routed experts by expectation, and the head. The
    embedding is a gather, the convolution counted apart."""
    d = sz["d"]
    routed = sz["top_k"] * sz["experts_held"] / sz["experts"]
    ff = {"dense": 3 * d * sz["mlp"],
          "experts": d * sz["experts"] + 3 * d * sz["shared_mlp"]
          + routed * 3 * d * sz["expert_mlp"]}
    return sum(_mixer_matmul_params(sz, mixer) + ff[kind]
               for mixer, kind in kinds(sz)) + d * sz["vocab"]


def param_count(sz: dict) -> int:
    shapes, _ = param_shapes(sz)
    return sum(math.prod(shape) for shape in
               weights.flat_shapes(shapes["params"]).values())


def flops_per_sample(sz: dict) -> float:
    """A token's share of a training step: 6 per matmul parameter it
    meets; attention by the causal half (q k^T at the score head's
    size, p v at the value head's; three forwards' worth); the rule by
    its recurrence and the convolution's taps likewise."""
    width = sz["heads"] * sz["kda_dim"]
    attention = 3.0 * attention_layers(sz) * sz["heads"] \
        * 2 * (sz["nope"] + sz["rope"] + sz["v_dim"]) * (sz["seq"] + 1) / 2
    rule = 3.0 * kda_layers(sz) * kda_flops.rule_forward_ops(
        1, 1, sz["heads"], sz["kda_dim"], sz["kda_dim"])
    conv = 3.0 * kda_layers(sz) * 2 * sz["conv"] * 3 * width
    return 6.0 * matmul_params_per_token(sz) + attention + rule + conv


def _mixer_shapes(sz: dict, kind: str, at: str):
    d, h = sz["d"], sz["heads"]
    if kind == "kda":
        width = h * sz["kda_dim"]
        return ({"in_proj_qkv": {"kernel": (d, 3 * width)},
                 "in_proj_f": {"kernel": (d, width)},
                 "in_proj_bz": {"kernel": (d, 2 * h)},
                 "conv": {"kernel": (sz["conv"], 3 * width)},
                 "A_log": (h,), "dt_bias": (width,),
                 "norm": {"scale": (sz["kda_dim"],)},
                 "out_proj": {"kernel": (width, d)}},
                {f"{at}/in_proj_qkv/kernel": d, f"{at}/in_proj_f/kernel": d,
                 f"{at}/in_proj_bz/kernel": d,
                 f"{at}/conv/kernel": sz["conv"],
                 f"{at}/out_proj/kernel": width})
    qk = sz["nope"] + sz["rope"]
    return ({"q": {"kernel": (d, h, qk)},
             "kv_a": {"kernel": (d, sz["kv_rank"] + sz["rope"])},
             "kv_norm": {"scale": (sz["kv_rank"],)},
             "kv_b": {"kernel": (sz["kv_rank"], h, sz["nope"] + sz["v_dim"])},
             "q_head_norm": {"scale": (qk,)}, "k_head_norm": {"scale": (qk,)},
             "gate": {"kernel": (d, h)},
             "o": {"kernel": (h, sz["v_dim"], d)}},
            {f"{at}/q/kernel": d, f"{at}/kv_a/kernel": d,
             f"{at}/kv_b/kernel": sz["kv_rank"], f"{at}/gate/kernel": d,
             f"{at}/o/kernel": h * sz["v_dim"]})


def param_shapes(sz: dict):
    """(``{"params": ..., "aux": {}}`` as nested shapes, fan-ins by
    path). Norm scales are ones and the router's bias zeros
    (``weights.leaf_value``), as the family starts them; ``A_log`` and
    ``dt_bias`` are unit normal draws beside ``assumed.gates``."""
    d, w, ws = sz["d"], sz["expert_mlp"], sz["shared_mlp"]
    held = sz["experts_held"]
    params = {"embed": {"embedding": (sz["vocab"], d)},
              "lm_head": {"kernel": (d, sz["vocab"])},
              "norm_f": {"scale": (d,)}}
    fan = {"params/embed/embedding": d, "params/lm_head/kernel": d}
    for i, (mixer, ff) in zip(sz["kept"], kinds(sz)):
        at = f"params/layer_{i}"
        shapes, f = _mixer_shapes(sz, mixer, f"{at}/mixer")
        fan.update(f)
        layer = {"norm1": {"scale": (d,)}, "norm2": {"scale": (d,)},
                 "mixer": shapes}
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
                            "down": (held, w, d)},
                "shared": {"gate": {"kernel": (d, ws)},
                           "up": {"kernel": (d, ws)},
                           "down": {"kernel": (ws, d)}}}
            fan.update({f"{at}/moe/router/kernel": d,
                        f"{at}/moe/experts/gate": d,
                        f"{at}/moe/experts/up": d,
                        f"{at}/moe/experts/down": w,
                        f"{at}/moe/shared/gate/kernel": d,
                        f"{at}/moe/shared/up/kernel": d,
                        f"{at}/moe/shared/down/kernel": ws})
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
    from horovod_tpu.models.ling3flash import Ling3FlashConfig, Ling3FlashLM
    return Ling3FlashLM(Ling3FlashConfig(
        vocab_size=sz["vocab"], hidden_size=sz["d"],
        published_layers=sz["published_layers"], kept_layers=sz["kept"],
        layer_group_size=sz["group_size"], first_k_dense=sz["dense_layers"],
        intermediate_size=sz["mlp"], num_heads=sz["heads"],
        kda_head_dim=sz["kda_dim"], short_conv_kernel_size=sz["conv"],
        kda_lower_bound=sz["lower"], a_log_init=sz["a_log_init"],
        dt_bias_init=sz["dt_bias_init"], q_lora_rank=None,
        kv_lora_rank=sz["kv_rank"], qk_nope_head_dim=sz["nope"],
        qk_rope_head_dim=sz["rope"], v_head_dim=sz["v_dim"],
        rope_theta=sz["theta"],
        moe_intermediate_size=sz["expert_mlp"],
        shared_intermediate_size=sz["shared_mlp"],
        n_routed_experts=sz["experts"], num_experts_per_tok=sz["top_k"],
        routed_scaling_factor=sz["scale"], n_group=sz["groups"],
        topk_group=sz["top_groups"],
        row_tier_headroom=sz["row_tier_headroom"],
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
    return train_steps.ling3flash_train_step(model, tx, mesh)


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
    loss_fn = train_steps.ling3flash_loss_fn(model)
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
    parameters: ``rule``, ``kda``, ``attend``, ``attention``,
    ``routing``, ``expert_layer`` (with ``held``/``offset`` to ask for
    another share, or all the experts, and ``shared`` to leave the
    shared expert out), ``mlp``, ``block``, ``head_loss``. The tests
    hold the program's modules against them one by one."""
    eps, d, h, dk = sz["eps"], sz["d"], sz["heads"], sz["kda_dim"]
    width = h * dk

    def rule(q, k, v, g, beta):
        """The recurrence, one position a step: q, k, g [B, S, H, Dk];
        v [B, S, H, Dv]; beta [B, S, H], for any number of heads.
        Checkpoints between chunks of ``REFERENCE_CHUNK`` positions."""
        bt, seq = q.shape[:2]
        chunk = REFERENCE_CHUNK if seq % REFERENCE_CHUNK == 0 else seq

        def step(state, xs):
            """``S^T x`` as a product and a sum over the key axis: an
            elementwise float32 sum takes no matmul precision."""
            qt, kt, vt, gt, bt_ = xs
            state = jnp.exp(gt)[..., None] * state
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
            one_chunk,
            jnp.zeros((bt, *q.shape[2:], v.shape[-1]), jnp.float32),
            tuple(timed(x) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(out.reshape(seq, bt, *v.shape[2:]), 0, 1)

    def kda(p, x):
        """A group of heads at a time, from the projections to the
        group's rows of ``W_o``, each group recomputed in the backward
        pass: what stands around the rule is position-wise and wide (the
        convolution's input alone is 805 MB at the cell's size, and a
        backward over all the heads keeps a dozen such: 11 GB)."""
        lead, seq = x.shape[:2], x.shape[1]
        groups = math.gcd(h, HEAD_GROUPS)
        hg = h // groups
        by_group = lambda w, parts: jnp.moveaxis(
            w.reshape(*w.shape[:-1], parts, groups, -1), -2, 0)
        l2 = lambda t: t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)

        @jax.checkpoint
        def one(y, w):
            w_qkv, taps, w_f, dt_bias, a_log, w_bz, w_o = w
            qkv = jnp.einsum("bsd,dpe->bspe", x, w_qkv)     # [B,S,3,hg*dk]
            padded = jnp.pad(
                qkv, ((0, 0), (sz["conv"] - 1, 0), (0, 0), (0, 0)))
            qkv = jax.nn.silu(sum(padded[:, j:j + seq] * taps[j]
                                  for j in range(sz["conv"])))
            q, k, v = (qkv[:, :, i].reshape(*lead, hg, dk) for i in range(3))
            f = (x @ w_f + dt_bias + sz["dt_bias_init"]).reshape(
                *lead, hg, dk)
            g = sz["lower"] * jax.nn.sigmoid(
                jnp.exp(a_log + sz["a_log_init"])[:, None] * f)
            bz = jnp.einsum("bsd,dpe->bspe", x, w_bz)       # [B,S,2,hg]
            o = rule(l2(q) * dk ** -0.5, l2(k), v, g,
                     jax.nn.sigmoid(bz[:, :, 0]))
            out = _rms(o, p["norm"], eps) \
                * jax.nn.sigmoid(bz[:, :, 1])[..., None]
            return y + out.reshape(*lead, hg * dk) @ w_o, None

        return jax.lax.scan(one, jnp.zeros_like(x), (
            by_group(p["in_proj_qkv"]["kernel"], 3),
            by_group(p["conv"]["kernel"], 3),
            by_group(p["in_proj_f"]["kernel"], 1)[:, :, 0],
            by_group(p["dt_bias"], 1)[:, 0],
            by_group(p["A_log"], 1)[:, 0],
            by_group(p["in_proj_bz"]["kernel"], 2),
            p["out_proj"]["kernel"].reshape(groups, hg * dk, d)))[0]

    def attend(q, k, v):
        """softmax(q k^T / sqrt(d) + causal) v, dense, ``d`` the score
        head's size: q, k [B, S, H, D]; v [B, S, H, Dv]. A block of
        queries at a time."""
        bt, seq, heads, hd = q.shape
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
        out = jax.lax.map(head, (flat(q), flat(k), flat(v)))
        return out.reshape(bt, heads, seq, -1).transpose(0, 2, 1, 3)

    def attention(p, x):
        """Latent attention: q straight from x; k and v through the
        normed latent, the rotary part shared by the heads; q and the
        assembled k normed by head; the rotary on their last ``rope``
        entries; a sigmoid gate a head. A group of heads at a time, as
        ``kda``."""
        nope, rope, rank = sz["nope"], sz["rope"], sz["kv_rank"]
        groups = math.gcd(h, HEAD_GROUPS)
        hg = h // groups
        kv = x @ p["kv_a"]["kernel"]
        c_kv = _rms(kv[..., :rank], p["kv_norm"], eps)
        k_rope = jnp.broadcast_to(kv[..., None, rank:],
                                  (*x.shape[:2], hg, rope))
        rotary = lambda t: jnp.concatenate(
            [t[..., :nope], _rope(t[..., nope:], sz["theta"])], -1)
        by_group = lambda w, axis: jnp.moveaxis(
            w.reshape(*w.shape[:axis], groups, hg, *w.shape[axis + 1:]),
            axis, 0)

        @jax.checkpoint
        def one(y, w):
            w_q, w_kv_b, w_gate, w_o = w
            q = jnp.einsum("bsd,dhe->bshe", x, w_q)
            kv_b = jnp.einsum("bsr,rhe->bshe", c_kv, w_kv_b)
            k = jnp.concatenate([kv_b[..., :nope], k_rope], -1)
            q = _rms(q, p["q_head_norm"], eps)
            k = _rms(k, p["k_head_norm"], eps)
            out = attend(rotary(q), rotary(k), kv_b[..., nope:]) \
                * jax.nn.sigmoid(x @ w_gate)[..., None]
            return y + jnp.einsum("bshe,hed->bsd", out, w_o), None

        return jax.lax.scan(one, jnp.zeros_like(x), (
            by_group(p["q"]["kernel"], 1), by_group(p["kv_b"]["kernel"], 1),
            by_group(p["gate"]["kernel"], 1), by_group(p["o"]["kernel"], 0)))[0]

    def routing(p, x):
        """``(weights [N, experts], chosen [N, top_k])`` of the tokens
        ``x`` [N, d]: sigmoid scores; ``score + bias`` in ``groups``
        groups of neighbours, a group's score the sum of its two
        largest, the ``top_groups`` best groups kept; the ``top_k``
        largest of ``score + bias`` inside them; the chosen scores
        divided by their sum and scaled; the weight of an expert not
        chosen is zero."""
        n, e, groups = x.shape[0], sz["experts"], sz["groups"]
        scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
        biased = scores + p["router"]["bias"]
        by_group = jnp.sort(biased.reshape(n, groups, e // groups), -1)
        group_score = by_group[..., -1] + by_group[..., -2]
        worst_kept = jnp.sort(group_score, -1)[:, groups - sz["top_groups"]]
        kept = jnp.repeat(group_score >= worst_kept[:, None], e // groups, -1)
        _, chosen = jax.lax.top_k(jnp.where(kept, biased, -jnp.inf),
                                  sz["top_k"])
        picked = scores * jnp.sum(jax.nn.one_hot(chosen, e), axis=1)
        return sz["scale"] * picked / jnp.sum(picked, -1, keepdims=True), \
            chosen

    def expert_layer(p, x, held=None, offset=None, shared=True):
        """The experts [offset, offset + held) one after another, each
        weighted by its router weight (zero where the token did not
        choose it), plus the shared expert, over a block of tokens at a
        time. ``p`` holds ``held`` experts' kernels."""
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

            start = _swiglu(p["shared"], xs) if shared \
                else jnp.zeros_like(xs)
            return jax.lax.scan(one, start, (*experts, ws.T))[0]

        return _by_rows(rows, jnp.concatenate([xf, share], -1)) \
            .reshape(x.shape)

    def mlp(p, x):
        return _by_rows(lambda t: _swiglu(p, t), x)

    def block(p, index, x, chosen=False):
        """Published layer ``index``; with ``chosen`` what its router
        chose, [tokens, top_k]."""
        hidden = _rms(x, p["norm1"], eps)
        x = x + (kda(p["mixer"], hidden)
                 if layer_kind(index, sz["group_size"]) == "kda"
                 else attention(p["mixer"], hidden))
        hidden = _rms(x, p["norm2"], eps)
        if index < sz["dense_layers"]:
            return x + mlp(p["mlp"], hidden)
        if chosen:
            return routing(p["moe"], hidden.reshape(-1, d))[1]
        return x + expert_layer(p["moe"], hidden)

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

    return {"rule": rule, "kda": kda, "attend": attend,
            "attention": attention, "routing": routing,
            "expert_layer": expert_layer, "mlp": mlp, "block": block,
            "head_loss": head_loss}


def reference_stages(sz: dict) -> dict:
    """The plain model as stages for ``check.StagedGradient``: the
    activation handed along the chain is the residual alone, and a
    block takes a row of the batch at a time (rows meet nowhere before
    the loss)."""
    fns = reference_fns(sz)

    def embed(p, aux, tokens):
        return p["embed"]["embedding"][tokens], {}

    def block_of(index):
        def block(p, aux, x):
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
