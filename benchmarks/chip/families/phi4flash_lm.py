"""Family ``phi4flash_lm``: a decoder-hybrid-decoder as
Phi-4-mini-flash-reasoning (``phi4flash``) lays it out: Mamba layers,
differential attention inside a window and over the whole prefix, and
a cross-decoder whose layers read one scan memory and one key-value
pair; trained on next-token cross-entropy through a head that is the
embedding's transpose.

The program under test is ``horovod_tpu.models.phi4flash.Phi4FlashLM``
with ``train_steps.phi4flash_train_step``; this file sizes it from a
configuration file, names its parameter shapes, makes its batch, counts
its FLOPs, and holds its plain float32 reference, which imports nothing
of the program.

What a later builder must know:

* **The kept layers carry their published index** (``kept_layers`` in
  the configuration file): the index fixes a layer's mixer
  (``layer_kind``) and its ``lambda_init``, so a cut of the model is a
  choice of indices, and the parameters of layer ``i`` are under
  ``layer_<i>``.
* **The reference's chain passes five things.**
  ``check.StagedGradient`` hands one activation from stage to stage;
  here it is ``(x, E, M, k, v)``: the residual, the embedding's table
  (the ``last`` stage multiplies by its transpose, and only through the
  chain does that use reach the ``first`` stage's backward, where it
  adds to the lookup's gradient: the table is one leaf), the scan
  memory of layer ``L/2`` and the key-value pair of layer ``L/2 + 1``
  (``None`` until a layer has made them). Blocks hand on untouched what
  they do not make.
* **The recurrence is a literal ``lax.scan`` over time**, nested by
  chunk under ``jax.checkpoint`` so that its backward fits (the states
  of 16,384 steps are 5.4 GB a layer); attention is the dense softmax
  under the two masks, a block of queries at a time; position-wise
  parts run a block of rows at a time under ``jax.checkpoint``.
* **FLOPs** count attention by the scores a mask allows (the causal
  half; the window's band), two maps a head pair, and the scan's
  operations from shapes (``chipbench/hybrid_flops.py``,
  ``chipbench/ssm_flops.py``).
* **The readers of this family match kernels by name**
  (``KERNEL_NAMES``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import hybrid_flops, ssm_flops, weights

SAMPLE = "tokens"

# How the device's ops line names the kernels (trace_reduce.short_name):
# by their Pallas ``name=``.
KERNEL_NAMES = {
    "flash": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
    "scan": ("ssm_scan_fwd", "ssm_scan_bwd"),
}

ROWS_AT_A_TIME = 2048     # of the reference's position-wise parts
REFERENCE_CHUNK = 128     # of its recurrence: checkpoints between chunks


def sizes(config: dict, per_chip_batch: int) -> dict:
    mamba = config["assumed"]["mamba"]
    kept = tuple(config["kept_layers"])
    if len(kept) != config["num_hidden_layers"]:
        raise ValueError(f"{len(kept)} kept layers {kept} against "
                         f"num_hidden_layers {config['num_hidden_layers']}")
    heads = config["num_attention_heads"]
    return {
        "vocab": config["vocab_size"],
        "d": config["hidden_size"],
        "heads": heads, "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // heads,
        "mlp": config["intermediate_size"],
        "window": config["sliding_window"],
        "published_layers": config["published"]["num_hidden_layers"],
        "kept": kept,
        "d_inner": mamba["d_inner"], "d_state": mamba["d_state"],
        "d_conv": mamba["d_conv"], "dt_rank": mamba["dt_rank"],
        "dt_bias_init": float(mamba["dt_bias_init"]),
        "eps": float(config["layer_norm_eps"]),
        "seq": config["assumed"]["sequence_length"],
        "per_chip_batch": per_chip_batch}


def samples_per_row(sz: dict) -> int:
    return sz["seq"]


def layer_kind(index: int, published_layers: int) -> str:
    """The mixer of published layer ``index`` (``mb_per_layer`` 2)."""
    half = published_layers // 2
    if index % 2 == 0:
        return "mamba" if index <= half else "gmu"
    if index < half:
        return "window"
    return "full" if index == half + 1 else "cross"


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def kinds(sz: dict) -> list:
    return [layer_kind(i, sz["published_layers"]) for i in sz["kept"]]


def scan_layers(sz: dict) -> int:
    return kinds(sz).count("mamba")


def attention_windows(sz: dict) -> list:
    """One entry a layer that calls the flash kernels: its window, or
    ``None`` for the whole prefix (the full layer and the cross
    layers)."""
    return [sz["window"] if k == "window" else None
            for k in kinds(sz) if k in ("window", "full", "cross")]


def _mixer_matmul_params(sz: dict, kind: str) -> int:
    d, di, hd = sz["d"], sz["d_inner"], sz["head_dim"]
    if kind == "mamba":
        return d * 2 * di + di * (sz["dt_rank"] + 2 * sz["d_state"]) \
            + sz["dt_rank"] * di + di * d
    if kind == "gmu":
        return 2 * d * di
    if kind == "cross":
        return 2 * d * d
    return d * (sz["heads"] + 2 * sz["kv_heads"]) * hd + d * d


def matmul_params_per_token(sz: dict) -> int:
    """Matmul parameters a token meets in one step: each layer's mixer
    and feed-forward, and the head (the embedding's transpose; the
    lookup itself is a gather)."""
    return sum(_mixer_matmul_params(sz, k) + 3 * sz["d"] * sz["mlp"]
               for k in kinds(sz)) + sz["d"] * sz["vocab"]


def param_count(sz: dict) -> int:
    shapes, _ = param_shapes(sz)
    return sum(math.prod(shape) for shape in
               weights.flat_shapes(shapes["params"]).values())


def flops_per_sample(sz: dict) -> float:
    """A token's share of a training step: 6 per matmul parameter it
    meets; attention by the scores the masks allow, two maps a head
    pair, three forwards' worth; the scans' operations likewise."""
    hd, seq = sz["head_dim"], sz["seq"]
    scores = sum(hybrid_flops.needed_scores(seq, w)
                 for w in attention_windows(sz))
    attention = 3.0 * sz["heads"] * scores \
        * hybrid_flops.forward_flops_per_score(hd, 2 * hd) / seq
    scan = 3.0 * scan_layers(sz) * ssm_flops.scan_forward_ops(
        1, 1, sz["d_inner"], sz["d_state"])
    return 6.0 * matmul_params_per_token(sz) + attention + scan


def _mixer_shapes(sz: dict, kind: str, at: str):
    d, di, n, r = sz["d"], sz["d_inner"], sz["d_state"], sz["dt_rank"]
    hd = sz["head_dim"]
    if kind == "mamba":
        return ({"in_proj": {"kernel": (d, 2 * di)},
                 "conv": {"kernel": (sz["d_conv"], di), "bias": (di,)},
                 "x_proj": {"kernel": (di, r + 2 * n)},
                 "dt_proj": {"kernel": (r, di), "bias": (di,)},
                 "A_log": (di, n), "D": (di,),
                 "out_proj": {"kernel": (di, d)}},
                {f"{at}/in_proj/kernel": d,
                 f"{at}/conv/kernel": sz["d_conv"],
                 f"{at}/x_proj/kernel": di, f"{at}/dt_proj/kernel": r,
                 f"{at}/out_proj/kernel": di})
    if kind == "gmu":
        return ({"in_proj": {"kernel": (d, di)},
                 "out_proj": {"kernel": (di, d)}},
                {f"{at}/in_proj/kernel": d, f"{at}/out_proj/kernel": di})
    shapes = {"out_proj": {"kernel": (d, d), "bias": (d,)},
              "subln": {"scale": (2 * hd,)},
              **{f"lambda_{v}": (hd,) for v in ("q1", "k1", "q2", "k2")}}
    # the four lambda vectors: normal with a deviation of 0.1
    fan = {f"{at}/out_proj/kernel": d,
           **{f"{at}/lambda_{v}": 100 for v in ("q1", "k1", "q2", "k2")}}
    name, width = ("q", sz["heads"] * hd) if kind == "cross" else \
        ("qkv", (sz["heads"] + 2 * sz["kv_heads"]) * hd)
    shapes[name] = {"kernel": (d, width), "bias": (width,)}
    fan[f"{at}/{name}/kernel"] = d
    return shapes, fan


def param_shapes(sz: dict):
    """(``{"params": ..., "aux": {}}`` as nested shapes, fan-ins by
    path)."""
    d, mlp = sz["d"], sz["mlp"]
    norm = lambda: {"scale": (d,), "bias": (d,)}
    params = {"embed": {"embedding": (sz["vocab"], d)}, "norm_f": norm()}
    fan = {"params/embed/embedding": d}
    for i, kind in zip(sz["kept"], kinds(sz)):
        at = f"params/layer_{i}"
        mixer, f = _mixer_shapes(sz, kind, f"{at}/mixer")
        fan.update(f)
        fan.update({f"{at}/fc1/kernel": d, f"{at}/fc2/kernel": mlp})
        params[f"layer_{i}"] = {
            "ln1": norm(), "ln2": norm(), "mixer": mixer,
            "fc1": {"kernel": (d, 2 * mlp)}, "fc2": {"kernel": (mlp, d)}}
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
    from horovod_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashLM
    return Phi4FlashLM(Phi4FlashConfig(
        vocab_size=sz["vocab"], hidden_size=sz["d"], num_heads=sz["heads"],
        num_kv_heads=sz["kv_heads"], intermediate_size=sz["mlp"],
        sliding_window=sz["window"],
        published_layers=sz["published_layers"], kept_layers=sz["kept"],
        d_inner=sz["d_inner"], d_state=sz["d_state"], d_conv=sz["d_conv"],
        dt_rank=sz["dt_rank"], dt_bias_init=sz["dt_bias_init"],
        layer_norm_eps=sz["eps"],
        dtype=jnp.bfloat16))


def program_shapes(model, sz: dict):
    tree = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, sz["seq"]), jnp.int32)),
        jax.random.key(0))
    return {"params": tree["params"], "aux": {}}


def injit_step(model, tx, mesh):
    from horovod_tpu.models import train_steps
    return train_steps.phi4flash_train_step(model, tx, mesh)


def injit_args(state: dict, batch: tuple) -> tuple:
    return (state["params"], state["opt"], *batch)


def injit_unpack(out, state: dict):
    params, opt, loss = out
    return {"params": params, "aux": state["aux"], "opt": opt}, loss


def program_loss(model):
    from horovod_tpu.models import train_steps
    loss_fn = train_steps.phi4flash_loss_fn(model)
    return lambda params, aux, tokens: (loss_fn(params, tokens), aux)


# -- the plain reference --------------------------------------------------

def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


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
    parameters: ``scan``, ``mamba``, ``attend``, ``diff_attention``,
    ``gmu``, ``mlp``, ``block``, ``head_loss``. The tests hold the
    program's modules against them one by one."""
    eps, hd = sz["eps"], sz["head_dim"]
    di, n, r = sz["d_inner"], sz["d_state"], sz["dt_rank"]
    pairs, kv_pairs = sz["heads"] // 2, sz["kv_heads"] // 2

    def scan(u, delta, a, b, c, skip):
        """The recurrence, one position a step: u, delta [B, S, D];
        a [D, N]; b, c [B, S, N]; skip [D]. Checkpoints between chunks
        of ``REFERENCE_CHUNK`` positions."""
        bt, seq, _ = u.shape
        chunk = REFERENCE_CHUNK if seq % REFERENCE_CHUNK == 0 else seq

        def step(h, xs):
            ut, dl, b_t, c_t = xs               # [B,D] [B,D] [B,N] [B,N]
            h = jnp.exp(dl[..., None] * a) * h \
                + (dl * ut)[..., None] * b_t[:, None, :]
            return h, jnp.sum(h * c_t[:, None, :], -1) + skip * ut

        @jax.checkpoint
        def one_chunk(h, xs):
            return jax.lax.scan(step, h, xs)

        timed = lambda x: x.transpose(1, 0, 2).reshape(
            seq // chunk, chunk, bt, x.shape[-1])
        _, y = jax.lax.scan(one_chunk, jnp.zeros((bt, di, n), jnp.float32),
                            tuple(timed(x) for x in (u, delta, b, c)))
        return y.reshape(seq, bt, di).transpose(1, 0, 2)

    def mamba(p, x):
        """``(out, memory)``: the memory is the scan's output before
        the gate."""
        uz = _by_rows(lambda t: t @ p["in_proj"]["kernel"], x)
        u, z = uz[..., :di], uz[..., di:]
        seq = u.shape[1]
        padded = jnp.pad(u, ((0, 0), (sz["d_conv"] - 1, 0), (0, 0)))
        u = jax.nn.silu(sum(
            padded[:, j:j + seq] * p["conv"]["kernel"][j]
            for j in range(sz["d_conv"])) + p["conv"]["bias"])
        rbc = u @ p["x_proj"]["kernel"]
        delta = jax.nn.softplus(
            rbc[..., :r] @ p["dt_proj"]["kernel"] + p["dt_proj"]["bias"]
            + sz["dt_bias_init"])
        y = scan(u, delta, -jnp.exp(p["A_log"]), rbc[..., r:r + n],
                 rbc[..., r + n:], p["D"])
        out = _by_rows(lambda t: (t[:, :di] * jax.nn.silu(t[:, di:]))
                       @ p["out_proj"]["kernel"],
                       jnp.concatenate([y, z], -1))
        return out, y

    def attend(q, k, v, window):
        """softmax(q k^T / sqrt(d) + mask) v, dense: q [B, S, H, D];
        k [B, S, Hkv, D]; v [B, S, Hkv, Dv]; query head h reads
        key-value head h // (H / Hkv). The mask is causal, and inside
        ``window`` keys where there is one. A block of queries at a
        time."""
        bt, seq, heads, d = q.shape
        group = heads // k.shape[2]
        rows = ROWS_AT_A_TIME if seq % ROWS_AT_A_TIME == 0 else seq
        keys = jnp.arange(seq)

        @jax.checkpoint
        def one(args):
            qi, ki, vi, start = args
            ahead = (start + jnp.arange(rows))[:, None] - keys[None, :]
            allowed = ahead >= 0
            if window is not None:
                allowed &= ahead < window
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

    def diff_attention(p, x, index, window, kv=None):
        """``(out, k, v)``: self-attention projects its own k and v;
        cross-attention (``kv`` given) projects q alone."""
        lead = x.shape[:2]
        if kv is None:
            qkv = _by_rows(lambda t: t @ p["qkv"]["kernel"]
                           + p["qkv"]["bias"], x)
            q = qkv[..., :2 * pairs * hd]
            k = qkv[..., 2 * pairs * hd:2 * (pairs + kv_pairs) * hd] \
                .reshape(*lead, kv_pairs, 2, hd)
            v = qkv[..., 2 * (pairs + kv_pairs) * hd:] \
                .reshape(*lead, kv_pairs, 2 * hd)
        else:
            q = x @ p["q"]["kernel"] + p["q"]["bias"]
            k, v = kv
        q = q.reshape(*lead, pairs, 2, hd)
        a1 = attend(q[..., 0, :], k[..., 0, :], v, window)
        a2 = attend(q[..., 1, :], k[..., 1, :], v, window)
        init = lambda_init(index)
        lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
            - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init
        diff = a1 - lam * a2
        normed = diff * jax.lax.rsqrt(
            jnp.mean(jnp.square(diff), -1, keepdims=True) + eps) \
            * p["subln"]["scale"] * (1.0 - init)
        out = normed.reshape(*lead, -1) @ p["out_proj"]["kernel"] \
            + p["out_proj"]["bias"]
        return out, k, v

    def gmu(p, x, memory):
        return _by_rows(
            lambda t: (t[:, :di] * jax.nn.silu(
                t[:, di:] @ p["in_proj"]["kernel"]))
            @ p["out_proj"]["kernel"], jnp.concatenate([memory, x], -1))

    def mlp(p, x):
        width = sz["mlp"]

        def rows(t):
            gate_up = _layer_norm(t, p["ln2"], eps) @ p["fc1"]["kernel"]
            return (gate_up[:, width:] * jax.nn.silu(gate_up[:, :width])) \
                @ p["fc2"]["kernel"]

        return x + _by_rows(rows, x)

    def block(p, index, x, memory, k, v):
        """Published layer ``index``: ``(x, memory, k, v)`` with what
        the layer makes put in its place."""
        kind = layer_kind(index, sz["published_layers"])
        h = _layer_norm(x, p["ln1"], eps)
        if kind == "mamba":
            mixed, y = mamba(p["mixer"], h)
            if index == sz["published_layers"] // 2:
                memory = y
        elif kind == "gmu":
            mixed = gmu(p["mixer"], h, memory)
        elif kind == "cross":
            mixed, _, _ = diff_attention(p["mixer"], h, index, None, (k, v))
        else:
            mixed, k_own, v_own = diff_attention(
                p["mixer"], h, index,
                sz["window"] if kind == "window" else None)
            if kind == "full":
                k, v = k_own, v_own
        return mlp(p, x + mixed), memory, k, v

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

    return {"scan": scan, "mamba": mamba, "attend": attend,
            "diff_attention": diff_attention, "gmu": gmu, "mlp": mlp,
            "block": block, "head_loss": head_loss}


def reference_stages(sz: dict) -> dict:
    """The plain model as stages for ``check.StagedGradient``. The
    activation handed along the chain is ``(x, E, M, k, v)`` (module
    docstring)."""
    fns = reference_fns(sz)

    def embed(p, aux, tokens):
        table = p["embed"]["embedding"]
        return (table[tokens], table, None, None, None), {}

    def block_of(index):
        def block(p, aux, carried):
            x, table, memory, k, v = carried
            x, memory, k, v = fns["block"](p, index, x, memory, k, v)
            return (x, table, memory, k, v), {}
        return block

    def last(p, carried, tokens):
        x, table = carried[:2]
        hidden = _layer_norm(x, p["norm_f"], sz["eps"])
        return fns["head_loss"](table, hidden[:, :-1], tokens[:, 1:])

    return {"first": (("embed",), embed),
            "blocks": [(f"layer_{i}", block_of(i)) for i in sz["kept"]],
            "last": (("norm_f",), last)}
