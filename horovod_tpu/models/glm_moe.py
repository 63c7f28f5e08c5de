"""DeepSeek-V3-style sparse decoder, as GLM-4.7-Flash (``glm4_moe_lite``)
lays it out: latent attention, a dropless expert layer that holds a
share of the experts, a shared expert, a multi-token-prediction module.

Pre-norm residual blocks, RMSNorm, no biases::

    x <- x + MLA(norm(x));  x <- x + FFN(norm(x))

FFN is a dense SwiGLU in the first ``first_k_dense`` layers and the
expert layer after them.

* **MLA** (:class:`LatentAttention`): queries and keys come through
  low-rank latents with an RMSNorm of their own; a rotary part that all
  heads share on the key side. In training the keys and values are
  expanded per head and go through ``flash_attention``; nothing is
  absorbed.
* **Expert layer** (:class:`ExpertLayer`, the one expert layer of
  every sparse model here; the configuration it is given says what
  differs between them): a float32 router over *all*
  ``n_routed_experts`` (``route``: logits at full precision, the
  choice by k rounds of max), here
  sigmoid scores and the top k of ``score + bias``, weights normalised
  over the k and scaled. The layer is told
  which experts it holds (``experts_held`` from ``expert_offset``: one
  chip's share under expert parallelism), routes over all of them and
  computes its own experts' part; what the absent experts would add is
  left out. **No assignment to a held expert is dropped, whatever the
  imbalance**: assignments are sorted by expert and the three products
  run over the held groups at their real sizes (``jax.lax.ragged_dot``,
  which the TPU compiler lowers to a grouped-matmul kernel of its own).
  The row buffer is static, so the layer picks the smaller of
  ``row_tiers`` (shares of tokens x k) that holds this step's
  assignments: twice the share of the experts held, or every
  assignment, a buffer of the first size at a time.
* **Multi-token prediction** (:class:`GlmMoeLM`, depth 1)::

      h'_i = W_eh [norm_e(Emb(t_{i+1})), norm_h(h_i)]

  with ``h_i`` the last layer's output before the final norm, one
  expert block of its own, a final norm of its own and the shared head.

The model returns the two hidden states and the expert layers' load
counts; ``train_steps.glm_moe_loss_fn`` turns them into
``CE(main) + lambda * CE(h'_i -> t_{i+2})``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import apply_rope, best_attention
from horovod_tpu.parallel import delta_epilogue, qkv_prologue


@dataclasses.dataclass(frozen=True)
class GlmMoeConfig:
    vocab_size: int = 154880
    num_layers: int = 47             # dense + expert layers, without MTP
    first_k_dense: int = 1
    hidden_size: int = 2048
    num_heads: int = 20
    q_lora_rank: Optional[int] = 768     # None: q = W_q x, no latent
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    # What else ``LatentAttention`` asks: an RMSNorm over the head on q
    # and on the assembled k, and a sigmoid gate a head on the output.
    qk_head_norm: bool = False
    head_output_gate: bool = False
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64       # the router's width
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    # What else ``ExpertLayer`` asks of its configuration: the family's
    # scores are sigmoids beside a correction bias, its shared expert
    # has no gate, and the chosen weights are divided by their bare sum.
    scoring: str = "sigmoid"
    shared_expert_gate: bool = False
    topk_weight_eps: float = 0.0
    n_group: int = 1                 # the choice is not limited to groups
    topk_group: int = 1
    expert_activation: str = "silu"
    row_tier_headroom: float = 2.0   # ``ROW_TIER_HEADROOM``
    # The share of the experts this chip holds: ids
    # [expert_offset, expert_offset + experts_held).
    experts_held: int = 64
    expert_offset: int = 0
    mtp_layers: int = 1              # 0 or 1
    mtp_loss_weight: float = 0.3     # lambda of the multi-token loss
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    dtype: Any = jnp.bfloat16

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def shared_intermediate_size(self) -> int:
        return self.moe_intermediate_size    # n_shared_experts 1


# Columns of an expert layer's counts, behind the held experts' own.
ABSENT, DROPPED = -2, -1
# The first tier of the expert layer's row buffer over the share of
# the experts held. A layer that holds an eighth of the experts sees an
# eighth of the assignments by expectation and 1.9 times that in its
# fullest expert (PERF.md, PR 27); over all the held experts together
# twice the expectation holds the sound case.
ROW_TIER_HEADROOM = 2.0


def row_tiers(experts_held: int, n_routed_experts: int,
              headroom: float = ROW_TIER_HEADROOM) -> tuple:
    """Static sizes of the expert layer's row buffer, as shares of
    tokens x k, ascending: ``headroom`` times the share of the experts
    held (at the default a quarter where an eighth is held, an eighth
    for a sixteenth), then 1.0, every assignment, so that nothing is
    ever dropped. A layer that holds half the experts or more has the
    one tier."""
    first = headroom * experts_held / n_routed_experts
    return (first, 1.0) if first < 1.0 else (1.0,)


def _norm(cfg, name: str):
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
                      param_dtype=jnp.float32, name=name)


def _dense(cfg, features, name: str, axis=-1):
    return nn.DenseGeneral(features, axis=axis, use_bias=False,
                           dtype=cfg.dtype, name=name)


class LatentAttention(nn.Module):
    """Latent attention (DeepSeek-V2's MLA), nothing absorbed: keys and
    values come through a normed latent of ``kv_lora_rank``, a rotary
    part of ``qk_rope_head_dim`` that all heads share on the key side,
    causal softmax attention at the score head's ``(nope + rope)^-1/2``
    through ``best_attention`` (the value head may be narrower than the
    score head).

    One body for every model with such a layer; the configuration says
    what differs. It is read for ``hidden_size``, ``num_heads``,
    ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``, ``rope_theta``, ``rms_norm_eps``, ``dtype`` and

    * ``q_lora_rank``: the width of the queries' normed latent (``q_a``,
      ``q_norm``, ``q_b``); ``None`` is ``q = W_q x`` (one kernel ``q``).
      GLM-4.7-Flash 768, Ling-3.0-flash ``None``;
    * ``qk_head_norm``: an RMSNorm over the head on q and on the
      assembled k ([nope | shared rotary part]), before the rotary turns
      their rotary parts (``q_head_norm``, ``k_head_norm``, one weight
      vector for all heads each). Ling-3.0-flash only;
    * ``head_output_gate``: the output of head ``h`` times
      ``sigmoid(x W_g)_h``, ``W_g`` the layer's own ``d -> heads``
      (``gate``). Ling-3.0-flash only."""

    cfg: Any

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h, nope, v_dim = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
        rope = cfg.qk_rope_head_dim
        with jax.named_scope("mla"):
            if cfg.q_lora_rank:
                c_q = _norm(cfg, "q_norm")(
                    _dense(cfg, cfg.q_lora_rank, "q_a")(x))
                q = _dense(cfg, (h, nope + rope), "q_b")(c_q)
            else:
                q = _dense(cfg, (h, nope + rope), "q")(x)
            kv = _dense(cfg, cfg.kv_lora_rank + rope, "kv_a")(x)
            c_kv = _norm(cfg, "kv_norm")(kv[..., :cfg.kv_lora_rank])
            k_rope = kv[..., None, cfg.kv_lora_rank:]          # [B,S,1,R]
            kv = _dense(cfg, (h, nope + v_dim), "kv_b")(c_kv)
            if cfg.qk_head_norm:
                k = jnp.concatenate(
                    [kv[..., :nope],
                     jnp.broadcast_to(k_rope, k_rope.shape[:2] + (h, rope))],
                    -1)
                q = _norm(cfg, "q_head_norm")(q)
                k = _norm(cfg, "k_head_norm")(k)
                k_nope, k_rope = k[..., :nope], k[..., nope:]
            q_rope = apply_rope(q[..., nope:], positions, cfg.rope_theta)
            k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
            q = jnp.concatenate([q[..., :nope], q_rope], -1)
            k = jnp.concatenate(
                [k_nope if cfg.qk_head_norm else kv[..., :nope],
                 jnp.broadcast_to(k_rope, k_rope.shape[:2] + (h, rope))], -1)
            out = best_attention(q, k, kv[..., nope:], True)
            if cfg.head_output_gate:
                gate = _dense(cfg, h, "gate")(x).astype(jnp.float32)
                out = out * jax.nn.sigmoid(gate)[..., None].astype(out.dtype)
            return _dense(cfg, cfg.hidden_size, "o", axis=(-2, -1))(out)


class SwiGLU(nn.Module):
    cfg: Any
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _dense(cfg, self.width, "gate")(x)
        up = _dense(cfg, self.width, "up")(x)
        return _dense(cfg, cfg.hidden_size, "down")(nn.silu(gate) * up)


# -- rows to their experts and back -----------------------------------------
# A row buffer holds ``cap`` rows in expert order: the held experts' runs
# one behind the other, token order inside a run, and behind them rows
# that belong to no assignment. ``Span`` is the buffer's index plan; the
# way there and the way back handle the buffer's rows and the tokens,
# never every assignment, and each is the other's transpose.

# Places a product of ``_run_sums``: the matrix unit's width.
RUN_BLOCK = 128


class Span(NamedTuple):
    order: jax.Array    # [cap] the assignment (token * k + choice) of a row
    live: jax.Array     # [cap] whether the row belongs to an assignment
    # the buffer's places: its rows in token order, filled up to whole
    # blocks of ``RUN_BLOCK`` with at least one place that sums nothing
    row: jax.Array      # [places] the live rows, their assignments ascending
    token: jax.Array    # [places] their tokens; N behind them, then -1
    first: jax.Array    # [N] the place where a token's run starts, or cap


def expert_order(group, held_n):
    """``(order, sizes)`` of the assignments' groups (a held expert's
    index, or ``held_n`` for an absent one): the assignments sorted by
    group, token order inside one, and how many each group has (what
    the plan cost was two scatters of tokens x k scalars, not the sort:
    the table below)."""
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    sizes = jnp.sum(group[:, None] == jnp.arange(held_n + 1), axis=0,
                    dtype=jnp.int32)
    return order, sizes


# ``span_of`` and ``_run_sums`` are jitted so that a step traces each
# once and not once a layer, tier and pass: traced every time they
# added 4.3 s to ``lfm2moe-injit-1chip``'s 34 s of warm set-up (PR 40).
@functools.partial(jax.jit, static_argnums=(2, 3))
def span_of(order, live, n, k) -> Span:
    """The plan of the buffer whose rows hold the assignments ``order``
    where ``live``: one sort of the buffer's rows back into token order
    and one scatter of the runs' starts; a token without a run starts
    at ``cap``, the first of the places that sum nothing."""
    cap = order.shape[0]
    fill = RUN_BLOCK - cap % RUN_BLOCK
    place = jnp.arange(cap, dtype=jnp.int32)
    assignment, row = jax.lax.sort(
        (jnp.where(live, order, n * k), place), num_keys=1)
    token = assignment // k
    starts = token != jnp.concatenate([jnp.full((1,), -1, token.dtype),
                                       token[:-1]])
    first = jnp.full((n,), cap, jnp.int32).at[
        jnp.where(starts, token, n)].set(place, mode="drop",
                                         unique_indices=True)
    return Span(order, live, jnp.pad(row, (0, fill)),
                jnp.pad(token, (0, fill), constant_values=-1), first)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rows_to_experts(x, span, k):
    """``x[span.order // k]``: [cap, D] rows in expert order from
    [N, D]."""
    return x[span.order // k]


def _rows_to_experts_fwd(x, span, k):
    return rows_to_experts(x, span, k), span


def _rows_to_experts_bwd(k, span, g):
    # rows behind the last live one hold whatever the products' backward
    # left there
    g = jnp.where(span.live[:, None], g, 0)
    return rows_from_experts(g, span, k), None


rows_to_experts.defvjp(_rows_to_experts_fwd, _rows_to_experts_bwd)


# The way back to token order and the plan, measured on v5e silicon (PR
# 40; rows of 2,048 bfloat16; ms): one call alone | with its transpose
# (``cap`` picks of [N, D]) | one layer's value and gradients under
# ``jax.checkpoint`` (two plans, three calls, one transpose), at the
# three cells' shapes, tokens x k and the buffer's rows:
#
#                                   32,768 x 4,       16,384 x 4,       16,384 x 10,
#                                   32,768            16,384            20,480
#   pick every assignment (PR 33)   8.46 10.04 49.3   2.04 2.42 27.9    5.54 5.99
#   add the buffer's rows (PR 33)   5.51  7.09        2.78 3.16         3.19 3.65 36.7
#   sorted add of the picked rows   4.53  6.11        2.28 2.65         2.55 3.01
#   runs, shifted reads, a select   4.92  6.50 38.7   1.88 2.26 24.0    3.48 3.93 31.6
#   runs, products, a select        3.34  4.92 36.3   0.76 1.13 23.7    0.94 1.39 27.8
#   runs, products, an empty place  2.72  4.30 34.9   1.14 1.52 23.4    1.31 1.77 27.5
#
# The last stands: a run's rows are summed by a 0/1 product a block of
# places, and a token without a run picks a place that sums nothing
# (alone it loses at the two smaller shapes, where 128 more places push
# the second pick's operand out of the 64 and 80 MiB the compiler
# copies to VMEM before it picks; in the layer it wins at all three).
# A pick of a 4 KB row costs 0.012 us from such an operand and 0.037 to
# 0.048 from one of 128 MiB; a row read at a shift of one to k - 1 rows
# is a copy of the array a shift (rows are packed two a sublane); a
# scattered or added row costs four picks. The plan: the stable sort,
# its inverse by a scatter and the sizes by a scatter-add took 1.97,
# 1.04 and 2.46 ms; the sort, the sizes by comparison and ``span_of``
# 0.74, 0.69 and 0.74. A sort of 131,072 pairs is 0.40 ms and one of
# 32,768 pairs 0.39, a cumulative sum 0.2 at any length, a scatter of
# 131,072 scalars 0.67 and their scatter-add into nine bins 1.2: a
# partition by counting (cumulative sums over [tokens, held] and over
# the tokens, then the inversion) read 1.15 to 1.45 where this plan
# reads 0.69 to 0.74, so the sort stays.

@functools.partial(jax.jit, static_argnums=2)
def _run_sums(p, token, k):
    """[places, D], whole blocks of ``RUN_BLOCK``: each row of ``p``
    plus the rows behind it of the same token (``token`` ascending, at
    most k alike), in float32, rounded once; nothing where the token is
    negative. A product with a 0/1 matrix a block; the block's last
    k - 1 rows, whose runs may cross into the next block, once more
    with that block's first k - 1 beside them."""
    block, h, d = RUN_BLOCK, k - 1, p.shape[-1]
    if h > block:
        raise ValueError(f"runs of {k} rows cross more than one block of "
                         f"{block}")
    t, rows = token.reshape(-1, block), p.reshape(-1, block, d)
    product = functools.partial(
        jnp.einsum, "bij,bjd->bid", preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    here = ((t[:, :, None] == t[:, None, :]) & (t[:, :, None] >= 0)
            & (jnp.arange(block)[:, None] <= jnp.arange(block))
            ).astype(p.dtype)
    sums = product(here, rows).astype(p.dtype)
    if h and t.shape[0] > 1:
        ahead = jnp.pad(t[1:, :h], ((0, 1), (0, 0)), constant_values=-1)
        crossing = (t[:, -h:, None] == ahead[:, None, :]).astype(p.dtype)
        sums = sums.at[:, -h:].set((
            product(here[:, -h:, -h:], rows[:, -h:]) + product(
                crossing, jnp.pad(rows[1:, :h], ((0, 1), (0, 0), (0, 0))))
        ).astype(p.dtype))
    return sums.reshape(-1, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rows_from_experts(rows, span, k):
    """[N, D] from [cap, D] rows in expert order, zeros where not
    ``span.live``: each token's held choices summed in float32. The
    live rows are picked into token order (``cap`` picks), a token's
    rows are summed where its run starts, and every token picks that
    sum, or a place that sums nothing (N picks)."""
    return _run_sums(rows[span.row], span.token, k)[span.first]


def _rows_from_experts_fwd(rows, span, k):
    from horovod_tpu.common import basics
    n = span.first.shape[0]
    basics.note_traced(
        "hvd_moe_rows_moved",
        "the differentiated expert layer traced last: assignments "
        "(tokens x k) a layer, rows of its first tier's buffer, and rows "
        "one call of the way back to token order picks",
        {"assignments": n * k, "buffer_rows": rows.shape[0],
         "picked_back": span.row.shape[0] + n})
    return rows_from_experts(rows, span, k), span


def _rows_from_experts_bwd(k, span, g):
    rows = jnp.where(span.live[:, None], rows_to_experts(g, span, k), 0)
    return rows, None


rows_from_experts.defvjp(_rows_from_experts_fwd, _rows_from_experts_bwd)


# -- the router --------------------------------------------------------------
# Exact work with fewer operations (PR 47): the choice is k rounds of
# max and not the sort ``jax.lax.top_k`` is on the chip, and a chosen
# score is what its round's reduction carried, not a gather whose
# transpose is a scatter. Measured on v5e silicon (PR 47; one call's
# value and gradients at the cells' shapes, tokens x d x experts, k;
# ms): the gather's form | this one | this one with bfloat16 rows
# against the float32 kernel's three bfloat16 parts, three single-pass
# products in place of the product at ``Precision.HIGHEST``:
#
#   16,384 x 2,048 x 512, 10 (softmax)            8.58  4.13  4.45
#   16,384 x 2,560 x 512, 8 of 4 of 8 groups      9.56  4.52  4.84
#   32,768 x 2,048 x 64, 4                        4.60  1.99  2.06
#   16,384 x 2,048 x 64, 4                        2.82  1.51  1.48
#
# The product stays at ``HIGHEST``: over rows cast up from bfloat16 the
# compiler leaves out the passes over the zero parts by itself (in the
# step 0.53 ms forward and 0.55 for dW, 34 GFLOP each: three passes at
# the chip's peak are 0.52; dx, two true float32 operands, 1.25), and
# the three-part form pays a write and a read of [N, 3 E] float32.
# In ``qwen3next-injit-1chip``'s step the gathers were 13.4 ms, the
# sorts 10.5, the scatter-adds 5.6 and the products 11.5 of ``moe.route``'s
# 43.9; eighty rounds of max are 1.8.


@jax.custom_vjp
def _logits(x, w):
    """``x @ w`` in float32 at full precision. Its own rule, so that
    the two backward products read the cotangent from memory: fused
    into them, the comparison of places that makes it ran once a tile
    of each product (``dW`` 1.33 ms where 0.55, ``dx`` 1.57 where 1.25,
    at 16,384 x 2,048 x 512 on the chip)."""
    return jnp.dot(x.astype(jnp.float32), w,
                   precision=jax.lax.Precision.HIGHEST)


def _logits_fwd(x, w):
    return _logits(x, w), (x, w)


def _logits_bwd(res, g):
    x, w = res
    g = jax.lax.optimization_barrier(g)
    product = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    return (product(g, w.T).astype(x.dtype),
            product(x.astype(jnp.float32).T, g).astype(w.dtype))


_logits.defvjp(_logits_fwd, _logits_bwd)


def _largest_below(choice, carried, value, index):
    """One round of max over the last axis: the largest entry of
    ``choice`` behind ``(value, index)`` in ``jax.lax.top_k``'s order
    (larger first, of equals the one that comes first), its place, and
    ``carried`` there; every entry where ``value`` is None. One
    reduction that reads ``choice`` once: nothing is masked in
    memory."""
    place = jax.lax.broadcasted_iota(jnp.int32, choice.shape, choice.ndim - 1)
    if value is not None:
        behind = (choice < value[..., None]) | (
            (choice == value[..., None]) & (place > index[..., None]))
        choice = jnp.where(behind, choice, -jnp.inf)

    def better(a, b):
        first = (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))
        return tuple(jnp.where(first, p, q) for p, q in zip(a, b))

    operands = (choice, place) + ((carried,) if carried is not None else ())
    init = (-jnp.inf, jnp.iinfo(jnp.int32).max, 0.0)[:len(operands)]
    out = jax.lax.reduce(
        operands, tuple(jnp.asarray(i, o.dtype) for i, o in
                        zip(init, operands)), better, (choice.ndim - 1,))
    return out[0], out[1], out[2] if carried is not None else out[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def choose(scores, choice, k):
    """``(chosen, picked)`` [N, k]: the places of the k largest entries
    of each row of ``choice`` [N, E] in ``jax.lax.top_k``'s order, by k
    rounds of max, and ``scores`` there (``choice`` None: the choice is
    over ``scores``, and a round's maximum is the chosen score).
    ``picked``'s transpose is a comparison of places, elementwise over
    [N, E]."""
    over, carried = (scores, None) if choice is None else (choice, scores)
    value = index = None
    chosen, picked = [], []
    for _ in range(k):
        value, index, score = _largest_below(over, carried, value, index)
        chosen.append(index)
        picked.append(score)
    return jnp.stack(chosen, axis=-1), jnp.stack(picked, axis=-1)


def _choose_fwd(scores, choice, k):
    chosen, picked = choose(scores, choice, k)
    return (chosen, picked), (chosen, jnp.arange(scores.shape[-1],
                                                 dtype=chosen.dtype))


def _choose_bwd(k, res, g):
    chosen, place = res
    column = lambda a, j: jax.lax.slice_in_dim(a, j, j + 1, axis=1)
    dscores = sum(jnp.where(place == column(chosen, j), column(g[1], j), 0)
                  for j in range(k))
    return dscores, None


choose.defvjp(_choose_fwd, _choose_bwd)


def _within_best_groups(biased, n_group: int, topk_group: int):
    """``biased`` [N, E] for a choice limited to groups (DeepSeek-V3's):
    the experts in ``n_group`` groups of neighbours, a group's score the
    sum of its two largest entries (two rounds of max), every entry
    outside the best ``topk_group`` groups (those that fewer than
    ``topk_group`` groups come before, in ``jax.lax.top_k``'s order) at
    minus infinity. ``n_group`` 1 gives ``biased`` itself."""
    if n_group == 1:
        return biased
    n, e = biased.shape
    grouped = biased.reshape(n, n_group, e // n_group)
    first, at, _ = _largest_below(grouped, None, None, None)
    score = first + _largest_below(grouped, None, first, at)[0]
    group = jnp.arange(n_group)
    before = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (group[None, :] < group[:, None]))
    kept = jnp.sum(before, axis=-1) < topk_group               # [N, g]
    return jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(n, e)


@functools.partial(jax.jit, static_argnums=3)
def route(xf, w_r, bias, cfg):
    """``(chosen, gates)`` [N, k] of rows ``xf`` [N, d] under the
    router's float32 kernel ``w_r`` [d, E] and correction bias (None
    under a softmax): float32 logits at full precision, the scores, the
    k experts of each row and their normalised, scaled weights.
    Jitted, so that a step traces it once and not once a layer and
    pass."""
    logits = _logits(xf, w_r)
    if cfg.scoring == "softmax":
        scores, choice = jax.nn.softmax(logits, axis=-1), None
    else:
        scores = jax.nn.sigmoid(logits)
        # (nothing is differentiated through a round of max)
        choice = _within_best_groups(jax.lax.stop_gradient(scores + bias),
                                     cfg.n_group, cfg.topk_group)
    chosen, picked = choose(scores, choice, cfg.num_experts_per_tok)
    # (the scaled scores first and no add of a zero: the lowered step
    # of a model without the constant is what it was)
    gates = cfg.routed_scaling_factor * picked
    total = jnp.sum(picked, axis=-1, keepdims=True)
    if cfg.topk_weight_eps:
        total = total + cfg.topk_weight_eps
    return chosen, gates / total


def _note_route(cfg) -> None:
    from horovod_tpu.common import basics
    basics.note_traced(
        "hvd_moe_route",
        "the expert layer's router traced last: rounds of max a call (k, "
        "and two for the groups' scores), and the sorts and gathers left "
        "in it",
        {"max_rounds": cfg.num_experts_per_tok + 2 * (cfg.n_group > 1),
         "sorts": 0, "gathers": 0})


class Routing(NamedTuple):
    """What an expert layer's router decided of its rows
    (``ExpertLayer.route``): all the layer needs of it."""
    order: jax.Array        # [N k] the assignments sorted by held expert
    sizes: jax.Array        # [held + 1] assignments a held expert, absent
    held_total: jax.Array   # assignments to held experts
    gate_rows: jax.Array    # [N k] an assignment's weight, 0 where absent
    plan: Optional[Span]    # the first tier's buffer, where made ahead


# What gates an expert's ``up`` rows: ``W_down (act(W_gate u) * W_up u)``.
EXPERT_ACTIVATIONS = {"silu": nn.silu, "relu": nn.relu}


class Router(nn.Module):
    """The router's parameters: the float32 kernel over all the experts
    and, where the scores are sigmoids, the correction bias that only
    the choice reads (the balancing rule that moves it is the
    trainer's, not the layer's); ``None`` under a softmax."""

    cfg: Any

    @nn.compact
    def __call__(self):
        cfg = self.cfg
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (cfg.hidden_size, cfg.n_routed_experts),
                            jnp.float32)
        if cfg.scoring == "softmax":
            return kernel, None
        bias = self.param("bias", nn.initializers.zeros,
                          (cfg.n_routed_experts,), jnp.float32)
        return kernel, bias


class HeldExperts(nn.Module):
    """The held experts' three kernels, [experts_held, in, out]."""

    cfg: Any

    @nn.compact
    def __call__(self):
        cfg = self.cfg
        d, width = cfg.hidden_size, cfg.moe_intermediate_size
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        return tuple(
            self.param(name, init, (cfg.experts_held, *dims),
                       jnp.float32).astype(cfg.dtype)
            for name, dims in (("gate", (d, width)), ("up", (d, width)),
                               ("down", (width, d))))


class ExpertLayer(nn.Module):
    """``(y, counts)``: the held experts' part of the routed result plus
    the shared expert, where the model has one; ``counts`` int32
    [experts_held + 2]: assignments to each held expert, to absent
    experts, and dropped (always 0).

    The router (``route``, scope ``moe.route``) makes float32 logits at
    full precision: a rounded score moves the choice, and the chip's
    default would run a float32 matmul in one bfloat16 pass. The k
    experts are ``jax.lax.top_k``'s to the place and the order under
    ties, found by k rounds of max (the chip sorts every row for a
    ``top_k``), and a chosen score is what its round carried: no
    gather, and its transpose a comparison, not a scatter.

    One body for every sparse model; the configuration says what
    differs. It is read for ``hidden_size``, ``moe_intermediate_size``,
    ``n_routed_experts`` (the router's width), ``num_experts_per_tok``,
    ``experts_held``, ``expert_offset``, ``dtype`` and

    * ``shared_intermediate_size``: the shared expert's width; 0 is a
      model without one (no ``shared`` parameters, no ``moe.shared``
      scope);

    * ``scoring``: ``"sigmoid"`` (scores are sigmoids, the choice reads
      ``score + bias``, a correction bias the layer owns) or
      ``"softmax"`` (scores are a softmax over all the experts, no
      bias);
    * ``n_group``, ``topk_group``: under sigmoid scores, the choice is
      limited to the best ``topk_group`` of ``n_group`` groups of
      neighbouring experts, a group scored by the sum of its two
      largest ``score + bias`` (``_within_best_groups``); ``n_group`` 1
      is a choice over all the experts;
    * ``row_tier_headroom``: the first tier of the row buffer over the
      share of the experts held (``row_tiers``; 2 but for
      Ling-3.0-flash, whose sixty-fourth of the experts is too small a
      share for twice its expectation to hold a step's assignments);
    * ``routed_scaling_factor``: what the normalised weights of a
      token's k choices are multiplied by;
    * ``topk_weight_eps``: what is added to the sum of a token's k
      chosen scores before they are divided by it (0: the bare sum);
    * ``shared_expert_gate``: whether the shared expert's output is
      multiplied by ``sigmoid(x w_s)``, ``w_s`` the layer's own
      ``d -> 1``;
    * ``expert_activation``: what gates a routed expert's ``up`` rows,
      ``"silu"`` or ``"relu"`` (``EXPERT_ACTIVATIONS``; the shared
      expert is a SwiGLU whatever it says).

    **The router may read other rows than the experts do**
    (``router_input``): SmallThinker takes its logits from the block's
    input and feeds the experts the post-attention normalised states,
    and its block calls ``route(x, plan_ahead=True)`` before attention
    and hands the :class:`Routing` in, so that the choice, the gates,
    the order by expert and the first tier's buffer plan stand in the
    traced program before the flash kernels. The router's gradient then
    flows into those rows. With neither set the layer lowers to what it
    did before it had them.

    What each model sets (the defaults are GLM-4.7-Flash's): GLM
    sigmoid, scale 1.8, a shared expert without a gate; Qwen3-Next
    softmax, scale 1, a gated shared expert; LFM2 sigmoid, scale 1, eps
    1e-6, no shared expert; Ling-3.0-flash sigmoid, scale 2.5, 8 groups
    of which 4, a shared expert without a gate; SmallThinker softmax,
    scale 1, no shared expert, ReLU, the router on the block's input."""

    cfg: Any

    def setup(self):
        cfg = self.cfg
        e, held_n = cfg.n_routed_experts, cfg.experts_held
        if not 0 <= cfg.expert_offset <= e - held_n:
            raise ValueError(
                f"experts [{cfg.expert_offset}, {cfg.expert_offset + held_n}"
                f") are not among the router's {e}")
        if cfg.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring {cfg.scoring!r}: sigmoid or softmax")
        if cfg.expert_activation not in EXPERT_ACTIVATIONS:
            raise ValueError(f"expert_activation {cfg.expert_activation!r}: "
                             f"one of {sorted(EXPERT_ACTIVATIONS)}")
        self.router = Router(cfg)
        self.experts = HeldExperts(cfg)
        if cfg.shared_intermediate_size:
            self.shared = SwiGLU(cfg, cfg.shared_intermediate_size)
            if cfg.shared_expert_gate:
                self.shared_gate = _dense(cfg, 1, None)

    def _caps(self, n: int) -> list:
        """The row buffer's static sizes for ``n`` tokens, a tier each."""
        cfg = self.cfg
        return [max(1, int(round(t * n * cfg.num_experts_per_tok)))
                for t in row_tiers(cfg.experts_held, cfg.n_routed_experts,
                                   cfg.row_tier_headroom)]

    def route(self, rows, plan_ahead: bool = False) -> Routing:
        """What the router decides of ``rows`` [..., d]: the choice, the
        gates and the order of the assignments by held expert; with
        ``plan_ahead`` the first tier's buffer plan too, so that a block
        whose router reads its input (SmallThinker) has all of it in
        the traced program before its attention call."""
        cfg = self.cfg
        k, held_n = cfg.num_experts_per_tok, cfg.experts_held
        xf = rows.reshape(-1, cfg.hidden_size)
        n = xf.shape[0]
        with jax.named_scope("moe.route"):
            # float32 at full precision: a rounded score moves the
            # choice (the chip's default runs an f32 matmul in bf16);
            # the choice without a sort, the weights without a gather
            w_r, bias = self.router()
            chosen, gates = route(xf, w_r, bias, cfg)
            _note_route(cfg)
            # kept only where a caller asks for ``intermediates``
            self.sow("intermediates", "chosen", chosen)

        with jax.named_scope("moe.dispatch"):
            local = chosen - cfg.expert_offset
            held = (local >= 0) & (local < held_n)             # [N, k]
            group = jnp.where(held, local, held_n).reshape(-1)
            order, sizes = expert_order(group, held_n)
            held_total = jnp.sum(sizes[:held_n])
            gate_rows = jnp.where(held, gates, 0.0).reshape(-1)
            plan = None
            if plan_ahead:
                cap = self._caps(n)[0]
                plan = span_of(order[:cap], jnp.arange(cap) < held_total,
                               n, k)
        return Routing(order, sizes, held_total, gate_rows, plan)

    def __call__(self, x, router_input=None):
        """``router_input``: the rows the router reads where they are
        not ``x`` ([..., d], as many as ``x`` has), or the
        :class:`Routing` that ``route`` made of them ahead; None routes
        on ``x`` itself."""
        cfg = self.cfg
        k, held_n = cfg.num_experts_per_tok, cfg.experts_held
        d = cfg.hidden_size
        xf = x.reshape(-1, d)
        n = xf.shape[0]
        routing = router_input if isinstance(router_input, Routing) \
            else self.route(xf if router_input is None else router_input)
        order, sizes, held_total, gate_rows, planned = routing
        activation = EXPERT_ACTIVATIONS[cfg.expert_activation]

        w_gate, w_up, w_down = self.experts()

        def span(first, gs, live, plan=None):
            """The held experts' part of the assignments ``first`` (a
            run of ``order``): ``gs`` says how many of its rows each
            expert has, ``live`` which rows belong to a held expert at
            all; ``plan`` where the buffer's was made ahead."""
            with jax.named_scope("moe.dispatch"):
                if plan is None:
                    plan = span_of(first, live, n, k)
                rows = rows_to_experts(xf, plan, k)
            with jax.named_scope("moe.experts"):
                hidden = activation(jax.lax.ragged_dot(rows, w_gate, gs)) \
                    * jax.lax.ragged_dot(rows, w_up, gs)
                out = jax.lax.ragged_dot(hidden, w_down, gs)
            with jax.named_scope("moe.combine"):
                # rows behind the last group are whatever the
                # buffer held: never let them meet a gradient
                out = jnp.where(live[:, None], out, 0) \
                    * gate_rows[first][:, None].astype(out.dtype)
                return rows_from_experts(out, plan, k)

        def routed(cap, plan=None):
            """The held experts' part through a row buffer of ``cap``."""
            def run(_):
                return span(order[:cap], sizes[:held_n],
                            jnp.arange(cap) < held_total, plan)
            return run

        def walked(cap):
            """Every assignment, ``cap`` rows of the sorted order at a
            time: the tier that drops nothing never holds more than the
            first tier's buffer. A slab is recomputed in the backward
            pass; one behind the last held row multiplies groups of no
            rows (a conditional around it would have the scan stack
            every slab's operands for the backward pass)."""
            slabs = -(-n * k // cap)
            padded = jnp.pad(order, (0, slabs * cap - n * k))
            ends = jnp.cumsum(sizes[:held_n])
            starts = ends - sizes[:held_n]

            @jax.checkpoint
            def slab(y, lo):
                first = jax.lax.dynamic_slice(padded, (lo,), (cap,))
                gs = jnp.clip(ends, lo, lo + cap) \
                    - jnp.clip(starts, lo, lo + cap)
                live = lo + jnp.arange(cap) < held_total
                return y + span(first, gs, live).astype(jnp.float32), None

            def run(_):
                y, _ = jax.lax.scan(
                    slab, jnp.zeros((n, d), jnp.float32),
                    jnp.arange(slabs, dtype=jnp.int32) * cap)
                return y.astype(cfg.dtype)
            return run

        caps = self._caps(n)
        if len(caps) == 1:
            tier = 0
            y = routed(caps[0], planned)(None)
        else:
            tier = jnp.sum(held_total > jnp.asarray(caps[:-1], jnp.int32))
            y = jax.lax.switch(
                tier, [routed(c, None if i else planned)
                       for i, c in enumerate(caps[:-1])]
                + [walked(caps[-2])], None)

        if cfg.shared_intermediate_size:
            with jax.named_scope("moe.shared"):
                shared = self.shared(xf)
                if cfg.shared_expert_gate:
                    shared = shared * jax.nn.sigmoid(
                        self.shared_gate(xf).astype(jnp.float32)) \
                        .astype(shared.dtype)
                y = y + shared
        # held assignments whose row lies behind the buffer that ran: the
        # last tier holds every row, so none
        dropped = jnp.maximum(
            held_total - jnp.asarray(caps, jnp.int32)[tier], 0)
        counts = jnp.concatenate([sizes, dropped[None]])
        return y.reshape(x.shape), counts


class Block(nn.Module):
    cfg: GlmMoeConfig
    use_moe: bool

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        x = x + LatentAttention(cfg, name="attn")(
            _norm(cfg, "ln1")(x), positions)
        h = _norm(cfg, "ln2")(x)
        if self.use_moe:
            y, counts = ExpertLayer(cfg, name="moe")(h)
        else:
            y = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(h)
            counts = jnp.zeros((cfg.experts_held + 2,), jnp.int32)
        return x + y, counts


# Kernels whose outputs a recomputed block makes again, by name.
_REMADE_KERNELS = (qkv_prologue.KERNEL_PREFIX, delta_epilogue.KERNEL_PREFIX)


def _keep_kernel_outputs(prim, *_, **params) -> bool:
    """Remat policy: a recomputed block keeps what its Pallas kernels
    wrote (flash attention's output and row statistics), so the
    backward pass does not run the forward kernel again. Not the delta
    rules' prologue nor their epilogue: q, k and v are 403 MB a layer
    at 16,384 tokens and the gated norm's y 134, a millisecond or two
    to make again."""
    return prim.name == "pallas_call" and not str(
        params.get("name")).startswith(_REMADE_KERNELS)


# Every block is recomputed in the backward pass: at the widths this
# model is published at, six blocks' activations do not fit a chip
# beside the state (the step without it fails to compile at 16.9 GB).
RematBlock = nn.remat(Block, policy=_keep_kernel_outputs)


class GlmMoeLM(nn.Module):
    cfg: GlmMoeConfig

    @nn.compact
    def __call__(self, tokens, positions=None, return_logits=False):
        """tokens [B, S] -> ``(hidden, mtp_hidden, counts)``: the
        pre-head states [B, S, D] of the main model (after its final
        norm) and of the multi-token module (``None`` without one; its
        position i predicts token i + 2, and its last position has no
        token to read and no target), and the expert layers' counts
        [layers, experts_held + 2], dense layers as zeros, the
        multi-token module's layer last. ``return_logits=True`` gives
        the main model's float32 logits [B, S, vocab] instead (training
        goes through ``lm_loss_from_hidden``, which never builds them)."""
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32)[None],
                tokens.shape)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="embed")
        x = embed(tokens)
        counts = []
        for i in range(cfg.num_layers):
            x, c = RematBlock(cfg, use_moe=i >= cfg.first_k_dense,
                              name=f"block_{i}")(x, positions)
            counts.append(c)
        hidden = _norm(cfg, "norm_f")(x)
        head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")
        if return_logits or self.is_initializing():
            logits = head(hidden.astype(jnp.float32))
            if return_logits:
                return logits
        mtp_hidden = None
        if cfg.mtp_layers:
            with jax.named_scope("mtp"):
                mtp_hidden, c = MultiTokenModule(cfg, name="mtp")(
                    x, embed(jnp.roll(tokens, -1, axis=1)), positions)
            counts.append(c)
        return hidden, mtp_hidden, jnp.stack(counts)


class MultiTokenModule(nn.Module):
    cfg: GlmMoeConfig

    @nn.compact
    def __call__(self, h, next_embedded, positions):
        cfg = self.cfg
        joined = jnp.concatenate(
            [_norm(cfg, "norm_e")(next_embedded), _norm(cfg, "norm_h")(h)],
            -1)
        x = _dense(cfg, cfg.hidden_size, "proj")(joined)
        x, counts = RematBlock(cfg, use_moe=True, name="block")(x, positions)
        return _norm(cfg, "norm_f")(x), counts
