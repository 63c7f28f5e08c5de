"""The two synthetic training programs, built once.

``bench.py`` measures them and ``chip_smoke.py`` proves they start on
the chip; both import the constructions here so they run the same
program: ``horovod_tpu.jax.DistributedOptimizer`` around optax
SGD-momentum inside a ``shard_map``'d step over the mesh's ``data``
axis (a size-1 mesh included, so the axis is always in scope for the
gradient pmean and the cross-replica batch norm), parameters placed
replicated and broadcast through the runtime at start the way a user's
script does, and donated buffers so XLA updates the weights in place.
"""

from __future__ import annotations

import collections
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu.jax as hvd
from horovod_tpu import spmd
from horovod_tpu.common import trace
from horovod_tpu.common.basics import active_runtime
from horovod_tpu.compat import jaxshim
from horovod_tpu.models.glm_moe import ABSENT, DROPPED, GlmMoeLM
from horovod_tpu.models.lfm2 import Lfm2MoeLM
from horovod_tpu.models.ling3flash import Ling3FlashLM
from horovod_tpu.models.olmo_hybrid import OlmoHybridLM
from horovod_tpu.models.phi4flash import Phi4FlashLM
from horovod_tpu.models.qwen3next import Qwen3NextLM
from horovod_tpu.models.resnet import ResNet50
from horovod_tpu.models.smallthinker import SmallThinkerLM
from horovod_tpu.models.transformer import (
    TransformerConfig, TransformerLM, lm_loss_from_hidden,
)

AXIS = "data"
RESNET_CLASSES = 1000


def distributed_sgd():
    """The optimizer of both programs: optax SGD-momentum wrapped so
    ``update`` first pmeans the gradients over the ``data`` axis."""
    return hvd.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), axis=AXIS)


def bench_lm(seq: int = 2048, num_layers: int = 12,
             attention_fn: Optional[Callable] = None) -> TransformerLM:
    """The transformer cell's model: d=2048 as 16 heads of 128,
    V=32000, bf16 (735.1M parameters at the published 12 layers).
    Only depth and sequence length may be cut."""
    return TransformerLM(TransformerConfig(
        vocab_size=32000, num_layers=num_layers, num_heads=16,
        head_dim=128, max_seq_len=seq, dtype=jnp.bfloat16,
        attention_fn=attention_fn))


def bench_resnet() -> ResNet50:
    """The ResNet-50 cell's model: bf16, batch norm statistics synced
    over the ``data`` axis."""
    return ResNet50(num_classes=RESNET_CLASSES, dtype=jnp.bfloat16,
                    axis_name=AXIS)


def lm_loss_fn(model: TransformerLM):
    """``(params, tokens) -> loss``: chunked next-token cross-entropy
    that never materializes the [B, S, V] logits."""
    def loss_fn(p, t):
        hidden = model.apply({"params": p}, t, return_hidden=True)
        return lm_loss_from_hidden(hidden, p["lm_head"]["kernel"], t)
    return loss_fn


def _apply(tx, grads, opt_state, params):
    """The optimizer's part of a step, under its own scope in a profile
    (the gradients' pmean inside ``tx.update`` is ``exchange`` there:
    ``horovod_tpu.jax.DistributedOptimizer`` names it)."""
    with jax.named_scope("optimizer"):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state


class _NotedLowered:
    """A lowered step whose ``compile()`` hands JAX's own ``Compiled``
    on and notes it with ``spmd`` (``spmd.noted_device_scopes``)."""

    def __init__(self, lowered):
        self._lowered = lowered

    def compile(self, *args, **kwargs):
        compiled = self._lowered.compile(*args, **kwargs)
        spmd.note_compiled(compiled)
        return compiled

    def __getattr__(self, name):
        return getattr(self._lowered, name)


class _NotedStep:
    """The jitted step under an armed trace: called and lowered as the
    ``jax.jit`` object it holds."""

    def __init__(self, jitted):
        self._jitted = jitted

    def __call__(self, *args, **kwargs):
        return self._jitted(*args, **kwargs)

    def lower(self, *args, **kwargs):
        return _NotedLowered(self._jitted.lower(*args, **kwargs))

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def _jit_step(step, mesh, donate_argnums):
    """``jax.jit`` of a step whose gradients are reduced over the
    mesh's ``data`` axis: where that axis spans chips the compiler is
    asked to run the all-reduces under the backward
    (``spmd.overlap_compiler_options``); over a mesh of one the step
    is jitted as ever. Where the program's spans are armed when the
    step is built (``HOROVOD_TPU_METRICS``, a world trace) the
    ``jax.jit`` object comes back inside a thin step whose
    ``lower(...).compile()`` notes the executable, so that
    ``spmd.noted_device_scopes()`` can say which scope each of its
    device ops belongs to; not armed, it is the ``jax.jit`` object
    itself and nothing is kept."""
    jitted = jax.jit(
        step, donate_argnums=donate_argnums,
        compiler_options=spmd.overlap_compiler_options(mesh, AXIS))
    return _NotedStep(jitted) if trace.spans_armed() else jitted


def _loss_train_step(loss_fn, tx, mesh):
    """The step of a model whose loss is ``loss_fn(params, tokens)``."""
    def step(p, os_, t):
        with jax.named_scope("loss"):
            loss, grads = jax.value_and_grad(loss_fn)(p, t)
        new_p, new_os = _apply(tx, grads, os_, p)
        with jax.named_scope("exchange"):
            loss = jax.lax.pmean(loss, AXIS)
        return new_p, new_os, loss

    rep = jaxshim.partition_spec()
    step = jaxshim.shard_map(
        step, mesh=mesh,
        in_specs=(rep, rep, jaxshim.partition_spec(AXIS)),
        out_specs=(rep, rep, rep))
    return _jit_step(step, mesh, donate_argnums=(0, 1))


def lm_train_step(model: TransformerLM, tx, mesh):
    """``jit(shard_map(step))`` with ``(params, opt_state)`` donated:
    ``(params, opt_state, tokens) -> (params, opt_state, loss)``. The
    loss is the mean over the mesh, not one shard's."""
    return _loss_train_step(lm_loss_fn(model), tx, mesh)


# The chunked head adds into the whole float32 gradient of the table
# (512 MB at 50,016 rows of 2,560) once a chunk. Measured on v5e silicon
# at the cell's shape (PR 31, **on the two-pass form**, whose backward
# made each chunk's logits again; one row of 16,384; ms a step, GB of
# temporaries): 1024 988.6, 5.15; 2048 979.3, 5.37; 4096 970.8, 5.53;
# 8192 980.4, 7.20. Not read again on the one-pass form (PR 34).
PHI4FLASH_HEAD_CHUNK = 4096


def phi4flash_loss_fn(model: Phi4FlashLM):
    """``(params, tokens) -> loss``: next-token cross-entropy through
    the chunked head **on the embedding's own rows** (the head is the
    embedding's transpose, so the loss's gradient and the lookup's add
    into one leaf). Chunks of ``PHI4FLASH_HEAD_CHUNK`` positions."""
    def loss_fn(p, t):
        hidden = model.apply({"params": p}, t)
        return lm_loss_from_hidden(hidden, p["embed"]["embedding"].T, t,
                                   chunk=PHI4FLASH_HEAD_CHUNK)
    return loss_fn


def phi4flash_train_step(model: Phi4FlashLM, tx, mesh):
    """The decoder-hybrid-decoder's step, ``lm_train_step``'s shape:
    ``(params, opt_state, tokens) -> (params, opt_state, loss)``, state
    donated. Every block is recomputed in the backward pass with its
    kernels' outputs kept (``phi4flash.RematBlock``); the scan's memory
    and the kept key-value pair pass from block to block beside the
    residual, and their readers' gradients add on the way back."""
    return _loss_train_step(phi4flash_loss_fn(model), tx, mesh)


def glm_moe_loss_fn(model: GlmMoeLM):
    """``(params, tokens) -> (loss, counts)``: the main next-token
    cross-entropy plus ``mtp_loss_weight`` times the multi-token one
    (position i of the module predicts token i + 2), both through the
    chunked head; ``counts`` are the expert layers' loads
    ([layers, experts_held + 2], ``glm_moe.ExpertLayer``)."""
    weight = model.cfg.mtp_loss_weight

    def loss_fn(p, t):
        hidden, mtp_hidden, counts = model.apply({"params": p}, t)
        head = p["lm_head"]["kernel"]
        loss = lm_loss_from_hidden(hidden, head, t)
        if mtp_hidden is not None:
            with jax.named_scope("mtp"):
                # the module's last position read no token and has no
                # target: the shifted ids are one shorter
                loss = loss + weight * lm_loss_from_hidden(
                    mtp_hidden[:, :-1], head, t[:, 1:])
        return loss, counts
    return loss_fn


def _counted_train_step(loss_fn, tx, mesh):
    """The step of a sparse model whose loss is ``loss_fn(params,
    tokens) -> (loss, counts)``."""
    def step(p, os_, t):
        with jax.named_scope("loss"):
            (loss, counts), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p, t)
        new_p, new_os = _apply(tx, grads, os_, p)
        with jax.named_scope("exchange"):
            loss = jax.lax.pmean(loss, AXIS)
            counts = jax.lax.psum(counts, AXIS)
        return new_p, new_os, loss, counts

    rep = jaxshim.partition_spec()
    step = jaxshim.shard_map(
        step, mesh=mesh,
        in_specs=(rep, rep, jaxshim.partition_spec(AXIS)),
        out_specs=(rep, rep, rep, rep))
    return _jit_step(step, mesh, donate_argnums=(0, 1))


def glm_moe_train_step(model: GlmMoeLM, tx, mesh):
    """``jit(shard_map(step))`` with ``(params, opt_state)`` donated:
    ``(params, opt_state, tokens) -> (params, opt_state, loss,
    counts)``. The loss is the mean over the mesh, the expert layers'
    counts their sum; feed the counts to :class:`MoeLoadFeed`, which
    never waits for a step."""
    return _counted_train_step(glm_moe_loss_fn(model), tx, mesh)


def qwen3next_loss_fn(model: Qwen3NextLM):
    """``(params, tokens) -> (loss, counts)``: next-token cross-entropy
    through the chunked head on the untied ``lm_head``; ``counts`` are
    the expert layers' loads ([layers, experts_held + 2])."""
    def loss_fn(p, t):
        hidden, counts = model.apply({"params": p}, t)
        return lm_loss_from_hidden(hidden, p["lm_head"]["kernel"], t), counts
    return loss_fn


def qwen3next_train_step(model: Qwen3NextLM, tx, mesh):
    """The hybrid linear-attention sparse decoder's step,
    ``glm_moe_train_step``'s shape: ``(params, opt_state, tokens) ->
    (params, opt_state, loss, counts)``, state donated, every block
    recomputed with its kernels' outputs kept
    (``qwen3next.RematBlock``), the counts for :class:`MoeLoadFeed`."""
    return _counted_train_step(qwen3next_loss_fn(model), tx, mesh)


# The chunked head over the embedding's own 8,192 rows of 2,048, 4 rows
# of 8,192 positions a step. Measured on v5e silicon (PR 39: value and
# both gradients of `lm_loss_from_hidden` alone at that shape, ms, then
# the compiler's temporaries in GB): chunk 512 21.78 and 0.135, 1,024
# 23.81 and 0.269, 2,048 26.02 and 0.437, 4,096 25.58 and 0.706, 8,192
# (one chunk a row) 21.64 and 1.208. The shortest is as fast as the
# longest and leaves the step, which needs 14.3 of the chip's 16.9 GB,
# a gigabyte more.
LFM2_HEAD_CHUNK = 512


def lfm2_loss_fn(model: Lfm2MoeLM):
    """``(params, tokens) -> (loss, counts)``: next-token cross-entropy
    through the chunked head **on the embedding's own rows** (as
    ``phi4flash_loss_fn``: the loss's gradient and the lookup's add into
    one leaf), chunks of ``LFM2_HEAD_CHUNK`` positions; ``counts`` are
    the layers' loads ([layers, experts_held + 2], a dense layer's as
    zeros)."""
    def loss_fn(p, t):
        hidden, counts = model.apply({"params": p}, t)
        return lm_loss_from_hidden(hidden, p["embed"]["embedding"].T, t,
                                   chunk=LFM2_HEAD_CHUNK), counts
    return loss_fn


def lfm2_train_step(model: Lfm2MoeLM, tx, mesh):
    """The convolution-attention sparse decoder's step,
    ``glm_moe_train_step``'s shape: ``(params, opt_state, tokens) ->
    (params, opt_state, loss, counts)``, state donated, every block
    recomputed with its kernels' outputs kept (``lfm2.RematBlock``),
    the counts for :class:`MoeLoadFeed`."""
    return _counted_train_step(lfm2_loss_fn(model), tx, mesh)


def ling3flash_loss_fn(model: Ling3FlashLM):
    """``(params, tokens) -> (loss, counts)``: next-token cross-entropy
    through the chunked head on the untied ``lm_head``; ``counts`` are
    the layers' loads ([layers, experts_held + 2], a dense layer's as
    zeros)."""
    def loss_fn(p, t):
        hidden, counts = model.apply({"params": p}, t)
        return lm_loss_from_hidden(hidden, p["lm_head"]["kernel"], t), counts
    return loss_fn


def ling3flash_train_step(model: Ling3FlashLM, tx, mesh):
    """The Kimi-delta-attention sparse decoder's step,
    ``glm_moe_train_step``'s shape: ``(params, opt_state, tokens) ->
    (params, opt_state, loss, counts)``, state donated, every block
    recomputed with its kernels' outputs kept
    (``ling3flash.RematBlock``), the counts for :class:`MoeLoadFeed`."""
    return _counted_train_step(ling3flash_loss_fn(model), tx, mesh)


def olmo_hybrid_loss_fn(model: OlmoHybridLM):
    """``(params, tokens) -> loss``: next-token cross-entropy through
    the chunked head on the untied ``lm_head``."""
    def loss_fn(p, t):
        hidden = model.apply({"params": p}, t)
        return lm_loss_from_hidden(hidden, p["lm_head"]["kernel"], t)
    return loss_fn


def olmo_hybrid_train_step(model: OlmoHybridLM, tx, mesh):
    """The dense hybrid decoder's step, ``lm_train_step``'s shape:
    ``(params, opt_state, tokens) -> (params, opt_state, loss)``, state
    donated, every block recomputed with its kernels' outputs kept
    (``olmo_hybrid.RematBlock``)."""
    return _loss_train_step(olmo_hybrid_loss_fn(model), tx, mesh)


def smallthinker_loss_fn(model: SmallThinkerLM):
    """``(params, tokens) -> (loss, counts)``: next-token cross-entropy
    through the chunked head on the untied ``lm_head``; ``counts`` are
    the layers' loads ([layers, experts_held + 2])."""
    def loss_fn(p, t):
        hidden, counts = model.apply({"params": p}, t)
        return lm_loss_from_hidden(hidden, p["lm_head"]["kernel"], t), counts
    return loss_fn


def smallthinker_train_step(model: SmallThinkerLM, tx, mesh):
    """The local-global sparse decoder's step, ``glm_moe_train_step``'s
    shape: ``(params, opt_state, tokens) -> (params, opt_state, loss,
    counts)``, state donated, every block recomputed with its kernels'
    outputs kept (``smallthinker.RematBlock``), the counts for
    :class:`MoeLoadFeed`."""
    return _counted_train_step(smallthinker_loss_fn(model), tx, mesh)


class MoeLoadFeed:
    """The host's end of the expert layers' counters: takes each step's
    counts as the device array the step returned and adds them to the
    metrics registry once they are there, **without waiting for a
    step**: an array still in flight stays queued until a later
    ``push`` or the registry's next snapshot finds it ready. With the
    metrics plane off (the default) a push keeps nothing.

    ``hvd_moe_assignments_total{held="1"|"0"}``: token-to-expert
    assignments to the experts this chip holds, and to the absent ones;
    ``hvd_moe_dropped_total``: assignments to a held expert that were
    not computed (the layer drops none); ``hvd_moe_steps_total``: steps
    counted so far; ``hvd_moe_expert_load_max_over_mean``: of the last
    step counted, the fullest held expert's load over the mean load,
    the worst expert layer's."""

    def __init__(self):
        reg = active_runtime().metrics
        self.enabled = bool(getattr(reg, "enabled", False))
        self._pending: collections.deque = collections.deque()
        if not self.enabled:
            return
        self._held = reg.counter(
            'hvd_moe_assignments_total{held="1"}',
            "token-to-expert assignments to experts this chip holds")
        self._absent = reg.counter(
            'hvd_moe_assignments_total{held="0"}',
            "token-to-expert assignments to experts held elsewhere")
        self._dropped = reg.counter(
            "hvd_moe_dropped_total",
            "assignments to a held expert that were not computed")
        self._steps = reg.counter("hvd_moe_steps_total",
                                  "steps whose expert loads were counted")
        self._skew = reg.gauge(
            "hvd_moe_expert_load_max_over_mean",
            "fullest held expert's load over the mean, the worst expert "
            "layer of the last step counted", agg="max")
        reg.add_collector(self.drain)

    def push(self, counts) -> None:
        if self.enabled:
            self._pending.append(counts)
            self.drain()

    def drain(self) -> None:
        """Count every queued step whose array is ready; never blocks."""
        while self._pending and self._pending[0].is_ready():
            counts = np.asarray(self._pending.popleft())
            loads = counts[:, :ABSENT]
            loads = loads[loads.sum(axis=1) > 0]     # expert layers only
            self._held.inc(int(loads.sum()))
            self._absent.inc(int(counts[:, ABSENT].sum()))
            self._dropped.inc(int(counts[:, DROPPED].sum()))
            self._steps.inc(1)
            if loads.size:
                self._skew.set(float(
                    (loads.max(axis=1) / loads.mean(axis=1)).max()))


def resnet_train_step(model: ResNet50, tx, mesh):
    """``jit(shard_map(step))`` with ``(params, batch_stats,
    opt_state)`` donated: ``(params, batch_stats, opt_state, images,
    labels) -> (params, batch_stats, opt_state, loss)``."""
    def loss_fn(p, bs, x, y):
        logits, updates = model.apply(
            {"params": p, "batch_stats": bs}, x, train=True,
            mutable=["batch_stats"])
        one_hot = jax.nn.one_hot(y, RESNET_CLASSES)
        loss = -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits) * one_hot, axis=-1))
        return loss, updates["batch_stats"]

    def step(p, bs, os_, x, y):
        with jax.named_scope("loss"):
            (loss, new_bs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p, bs, x, y)
        new_p, new_os = _apply(tx, grads, os_, p)
        with jax.named_scope("exchange"):
            loss = jax.lax.pmean(loss, AXIS)
        return new_p, new_bs, new_os, loss

    rep = jaxshim.partition_spec()
    batch = jaxshim.partition_spec(AXIS)
    step = jaxshim.shard_map(
        step, mesh=mesh, in_specs=(rep, rep, rep, batch, batch),
        out_specs=(rep, rep, rep, rep))
    return _jit_step(step, mesh, donate_argnums=(0, 1, 2))


def synthetic_tokens(seed: int, batch: int, seq: int, vocab: int, mesh):
    """A fixed random token batch, dim 0 split over the mesh."""
    tokens = jax.random.randint(
        jax.random.key(seed), (batch, seq), 0, vocab, jnp.int32)
    return jax.device_put(tokens, spmd.batch_sharding(mesh))


def synthetic_images(seed: int, batch: int, mesh, image_size: int = 224):
    """ImageNet-shaped random images and labels, split over the mesh."""
    k_img, k_lab = jax.random.split(jax.random.key(seed))
    images = jax.random.normal(
        k_img, (batch, image_size, image_size, 3), jnp.bfloat16)
    labels = jax.random.randint(
        k_lab, (batch,), 0, RESNET_CLASSES, jnp.int32)
    sharding = spmd.batch_sharding(mesh)
    return (jax.device_put(images, sharding),
            jax.device_put(labels, sharding))


def _init_replicated(init_fn, mesh, seed, batch):
    return jax.jit(init_fn,
                   out_shardings=spmd.replicated_sharding(mesh))(
        jax.random.key(seed), batch)


def lm_train_state(model: TransformerLM, tx, mesh, tokens, seed: int = 0):
    """``(params, opt_state)`` replicated over the mesh, the parameters
    broadcast through the runtime as a user's start-up does."""
    variables = _init_replicated(model.init, mesh, seed, tokens)
    params = variables["params"]
    opt_state = tx.init(params)
    return hvd.broadcast_parameters(params, root_rank=0), opt_state


def resnet_train_state(model: ResNet50, tx, mesh, images, seed: int = 0):
    """``(params, batch_stats, opt_state)`` replicated over the mesh."""
    variables = _init_replicated(
        lambda r, x: model.init(r, x, train=True), mesh, seed, images)
    params = variables["params"]
    opt_state = tx.init(params)
    return (hvd.broadcast_parameters(params, root_rank=0),
            variables["batch_stats"], opt_state)
