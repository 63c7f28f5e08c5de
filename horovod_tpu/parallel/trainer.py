"""Composed dp x tp x sp training — one jitted step over one mesh.

The reference's trainer story is "wrap your optimizer"
(reference: horovod/torch/__init__.py:42 DistributedOptimizer): the
gradient leaves the framework, is averaged by the runtime, and comes
back. The TPU-native story is stronger: parameters and batch carry
shardings, the step is jitted once over the mesh, and XLA inserts and
overlaps every collective (gradient all-reduce for dp, activation psum
for tp, kv-ring permutes for sp). This module is the composition point.

No manual gradient psum appears anywhere: with replicated parameters
and a dim-0-sharded batch, GSPMD derives the gradient all-reduce that
Horovod's whole background runtime exists to perform.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from horovod_tpu.parallel.sharding import (
    ShardingRules, fsdp_sharding, infer_sharding, transformer_tp_rules,
)


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    data_axis: str = "data"
    model_axis: Optional[str] = "model"   # None = no tensor parallelism
    seq_axis: Optional[str] = None        # None = no sequence parallelism
    expert_axis: Optional[str] = None     # None = no expert parallelism
    fsdp_axis: Optional[str] = None       # None = no parameter sharding
    # (fsdp_axis may equal data_axis: classic FSDP shards weights over
    # the data ranks; GSPMD inserts the per-layer all-gathers and the
    # gradient reduce-scatters, and optimizer state follows the
    # parameter shardings — see parallel.sharding.fsdp_sharding)
    # Sequence parallelism needs a ring attention_fn in the model config
    # (parallel.make_ring_attention) — injected there, not a flag here,
    # because the attention implementation lives in the module tree.
    donate_state: bool = True


class Trainer:
    """Builds init/step for a flax module over a mesh.

    ``loss_fn(apply_fn, params, batch) -> scalar`` defines the task;
    defaults to next-token LM loss on ``batch['tokens']``.
    """

    def __init__(self, module, mesh, tx,
                 config: TrainerConfig = TrainerConfig(),
                 rules: Optional[ShardingRules] = None,
                 loss_fn: Optional[Callable] = None,
                 batch_spec=None):
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.compat import jaxshim
        self.module = module
        self.mesh = mesh
        self.tx = tx
        self.config = config
        if rules is None:
            m = (config.model_axis
                 if config.model_axis
                 and config.model_axis in mesh.axis_names else None)
            ep = (config.expert_axis
                  if config.expert_axis
                  and config.expert_axis in mesh.axis_names else None)
            # EP works with or without TP: PartitionSpec treats a None
            # axis entry as replicated, so the rules compose naturally.
            rules = (transformer_tp_rules(m, expert_axis=ep)
                     if (m or ep) else ShardingRules([]))
        self.rules = rules
        self.loss_fn = loss_fn or _default_lm_loss
        if batch_spec is None:
            if config.seq_axis and config.seq_axis in mesh.axis_names:
                batch_spec = P(config.data_axis, config.seq_axis)
            else:
                batch_spec = P(config.data_axis)
        self.batch_sharding = jaxshim.named_sharding(mesh, batch_spec)
        self._replicated = jaxshim.named_sharding(mesh, P())
        self._step = None
        self._param_shardings = None
        self._state_shardings = None

    # ------------------------------------------------------------------
    def init(self, rng, sample_batch):
        """Initialize params + opt state, already sharded per the rules."""
        batch = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self.batch_sharding), sample_batch)
        inputs = batch["tokens"] if isinstance(batch, dict) else batch

        params = jax.jit(self.module.init)(rng, inputs)
        self._param_shardings = infer_sharding(params, self.rules, self.mesh)
        fa = self.config.fsdp_axis
        if fa is not None:
            if fa not in self.mesh.axis_names:
                raise ValueError(
                    f"fsdp_axis {fa!r} is not a mesh axis "
                    f"{self.mesh.axis_names}; parameters would silently "
                    f"stay replicated")
            self._param_shardings = fsdp_sharding(
                params, self.mesh, axis=fa, base=self._param_shardings)
        params = jax.tree_util.tree_map(jax.device_put, params,
                                        self._param_shardings)
        # Optimizer moments must be co-sharded with their parameters
        # (XLA does not propagate input shardings through zeros_like, so
        # an unconstrained init would replicate them — forfeiting the
        # fsdp/tp memory win). Pin out_shardings by matching each state
        # leaf to its parameter via path suffix + shape.
        opt_shardings = _opt_state_shardings(
            self.tx, params, self._param_shardings, self.mesh)
        opt_state = jax.jit(self.tx.init,
                            out_shardings=opt_shardings)(params)
        # The counter is committed to the mesh like the rest of the
        # state, and the step returns the state under the shardings it
        # took (`step_fn`): its second call finds the first's executable.
        self._state_shardings = {
            "params": self._param_shardings, "opt_state": opt_shardings,
            "step": self._replicated}
        return {"params": params, "opt_state": opt_state,
                "step": jax.device_put(jnp.zeros((), jnp.int32),
                                       self._replicated)}

    # ------------------------------------------------------------------
    def step_fn(self):
        """The jitted train step (built once, cached)."""
        if self._step is not None:
            return self._step

        def step(state, batch):
            def loss_of(p):
                return self.loss_fn(self.module.apply, p, batch)
            loss, grads = jax.value_and_grad(loss_of)(state["params"])
            updates, new_opt = self.tx.update(grads, state["opt_state"],
                                              state["params"])
            import optax
            new_params = optax.apply_updates(state["params"], updates)
            return {"params": new_params, "opt_state": new_opt,
                    "step": state["step"] + 1}, loss

        donate = (0,) if self.config.donate_state else ()
        self._step = jax.jit(step, donate_argnums=donate,
                             out_shardings=(self._state_shardings, None))
        return self._step

    def train_step(self, state, batch):
        # device_put is a no-op for arrays already resident with an
        # equivalent sharding; host (numpy) batches are uploaded each
        # call — place a fixed batch on the mesh once yourself when
        # benchmarking (see examples/transformer_long_context.py: on
        # remote-attached TPUs the per-step upload dwarfs the step).
        batch = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self.batch_sharding), batch)
        return self.step_fn()(state, batch)


def _opt_state_shardings(tx, params, param_shardings, mesh):
    """NamedSharding tree for ``tx.init(params)``: param-shaped state
    leaves (Adam/momentum moments, keyed by the same sub-paths as the
    parameter tree) take their parameter's sharding; everything else
    (step counters, scalars) is replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.compat import jaxshim

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    flat_sh = jax.tree_util.tree_leaves(
        param_shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
    # Longest key first: "...['z']['w']" must win over a bare "...['w']"
    # when both are suffixes of a state leaf's path and shapes collide.
    keyed = sorted(
        ((jax.tree_util.keystr(path), leaf.shape, sh)
         for (path, leaf), sh in zip(flat, flat_sh)),
        key=lambda t: len(t[0]), reverse=True)

    abs_state = jax.eval_shape(tx.init, params)
    replicated = jaxshim.named_sharding(mesh, P())

    def one(path, leaf):
        ks = jax.tree_util.keystr(path)
        for pks, shape, sh in keyed:
            if ks.endswith(pks) and getattr(leaf, "shape", None) == shape:
                return sh
        return replicated

    return jax.tree_util.tree_map_with_path(one, abs_state)


_MOE_AUX_WEIGHT = 0.01  # Switch Transformer's alpha


def _lm_loss_with_moe_aux(apply_fn, params, batch, task_loss,
                          **apply_kwargs):
    """Shared LM-loss scaffolding: extract tokens, apply with sowed
    intermediates, add the Switch load-balancing auxiliary (zero for
    dense models). ``task_loss(output, tokens)`` computes the
    next-token loss from whatever ``apply_fn`` returned. Without the
    aux term a top-1 router collapses onto one expert and the fixed
    capacity silently drops the overflow tokens."""
    from horovod_tpu.models.transformer import moe_aux_loss
    tokens = batch["tokens"] if isinstance(batch, dict) else batch
    output, mutated = apply_fn(params, tokens,
                               mutable=["intermediates"],
                               **apply_kwargs)
    loss = task_loss(output, tokens)
    aux = moe_aux_loss(mutated.get("intermediates", {}))
    return loss + _MOE_AUX_WEIGHT * aux


def make_chunked_lm_loss(chunk: int = 1024):
    """Trainer ``loss_fn`` for big-vocab / long-context TransformerLM:
    next-token loss via :func:`models.transformer.lm_loss_from_hidden`,
    so the full [B, S, vocab] fp32 logits never exist in HBM. Same
    MoE-aux handling as the default loss.

    ``Trainer(model, mesh, tx, loss_fn=make_chunked_lm_loss(1024))``.
    """
    from horovod_tpu.models.transformer import lm_loss_from_hidden

    def loss_fn(apply_fn, params, batch):
        def task_loss(hidden, tokens):
            head_kernel = params["params"]["lm_head"]["kernel"]
            return lm_loss_from_hidden(hidden, head_kernel, tokens,
                                       chunk=chunk)
        return _lm_loss_with_moe_aux(apply_fn, params, batch,
                                     task_loss, return_hidden=True)

    return loss_fn


def _default_lm_loss(apply_fn, params, batch):
    """Next-token LM loss from full logits (see _lm_loss_with_moe_aux
    for the shared MoE-aux scaffolding)."""
    from horovod_tpu.models.transformer import lm_loss
    return _lm_loss_with_moe_aux(apply_fn, params, batch, lm_loss)

