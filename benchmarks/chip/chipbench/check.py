"""How ``correct` is decided: the program's first three steps against
the plain reference's, number by number, each with a limit of its own.

The numbers, for a state ``{"params", "aux"}`` driven through three
steps of SGD with momentum on one repeated batch:

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_norm_gap``: per leaf, the norm of the first gradient as the
  optimizer got it (its momentum after one step, which starts at
  zero) against the reference's, the worst leaf;
* ``update_norm_gap``: per leaf, the norm of the change after three
  steps (parameters and, where the model has them, running
  statistics) against the reference's, the worst leaf.

A leaf's gap is the difference of the two norms over the reference's
norm of that leaf or of the median leaf, whichever is larger: some
gradients are all but zero.
"""

from __future__ import annotations

import functools
import statistics
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp


def leaf_norms(tree) -> List[float]:
    """The 2-norm of every leaf, in float32, as floats on the host."""
    norms = jax.jit(lambda t: [
        jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
        for l in jax.tree_util.tree_leaves(t)])(tree)
    return [float(n) for n in norms]


def diff_norms(after, before) -> List[float]:
    norms = jax.jit(lambda a, b: [
        jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])(after, before)
    return [float(n) for n in norms]


def worst_leaf_gap(got: Sequence[float], want: Sequence[float]) -> float:
    if len(got) != len(want):
        raise ValueError(f"{len(got)} leaves against {len(want)}")
    floor = statistics.median(want)
    return max(abs(g - w) / max(w, floor) for g, w in zip(got, want))


def loss_gap(got: Sequence[float], want: Sequence[float]) -> float:
    return max(abs(g - w) / abs(w) for g, w in zip(got, want))


class StagedGradient:
    """Loss and gradients of a model given as stages, one jitted call
    a stage: ``first`` (the batch to activations), ``blocks``
    (activations to activations, one after another) and ``last``
    (activations to the loss). Only the blocks' inputs are kept between
    the forward and the backward sweep, and a block's own temporaries
    live for one call, so the plain float32 model fits beside its
    parameters at the timed batch.

    ``stages`` is ``{"first": (keys, fn(p, a, *batch) -> (x, a')),
    "blocks": [(key, fn(p, a, x) -> (x, a')), ...], "last": (keys,
    fn(p, x, *batch) -> loss)}``; ``keys`` name the top-level entries
    of the parameters (and running statistics) a stage owns."""

    def __init__(self, stages: dict):
        self.stages = stages
        self._jitted: dict = {}

    def _jit(self, fn, kind: str, make):
        key = (id(fn), kind)
        if key not in self._jitted:
            self._jitted[key] = jax.jit(make(fn))
        return self._jitted[key]

    @staticmethod
    def _block_bwd(fn):
        def bwd(p, a, x, g):
            _, vjp, _ = jax.vjp(lambda p_, x_: fn(p_, a, x_), p, x,
                                has_aux=True)
            return vjp(g)
        return bwd

    @staticmethod
    def _first_bwd(fn):
        def bwd(p, a, g, *batch):
            _, vjp, _ = jax.vjp(lambda p_: fn(p_, a, *batch), p,
                                has_aux=True)
            return vjp(g)[0]
        return bwd

    def __call__(self, params: dict, aux: dict, batch: tuple):
        """``(loss, new_aux, grads)``."""
        keys, first = self.stages["first"]
        sub = lambda tree, ks: {k: tree[k] for k in ks if k in tree}
        x, new_first = self._jit(first, "fwd", lambda f: f)(
            sub(params, keys), sub(aux, keys), *batch)
        new_aux = dict(new_first)
        inputs = []
        for key, fn in self.stages["blocks"]:
            inputs.append(x)
            x, new_aux_k = self._jit(fn, "fwd", lambda f: f)(
                params[key], aux.get(key, {}), x)
            if key in aux:
                new_aux[key] = new_aux_k
        last_keys, last = self.stages["last"]
        loss, (g_last, g) = self._jit(
            last, "grad",
            lambda f: jax.value_and_grad(f, argnums=(0, 1)))(
                sub(params, last_keys), x, *batch)
        grads = dict(g_last)
        del x
        for key, fn in reversed(self.stages["blocks"]):
            grads[key], g = self._jit(fn, "bwd", self._block_bwd)(
                params[key], aux.get(key, {}), inputs.pop(), g)
        grads.update(self._jit(first, "bwd", self._first_bwd)(
            sub(params, keys), sub(aux, keys), g, *batch))
        return loss, new_aux, grads


def reference_steps(stages: dict, make_state, shards: Sequence[tuple],
                    lr: float, momentum: float, steps: int,
                    mean_loss: bool = False) -> dict:
    """Follow ``steps`` steps of SGD with momentum in float32 under
    ``highest`` matmul precision, from the state ``make_state()``
    gives (called again at the end for the change: the steps update in
    place). ``shards`` are the batches of the ranks in turn: the
    gradient is the mean of the shards' gradients, the loss the first
    shard's (each rank reports its own), and the running statistics
    the first shard's too. With ``mean_loss`` the shards are one
    in-jit batch's rows, a chip's share each, and the loss is their
    mean, as the step over the mesh reports it."""
    grad_fn = StagedGradient(stages)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def apply(params, trace, grads):
        trace = jax.tree_util.tree_map(
            lambda t, g: g / len(shards) + momentum * t, trace, grads)
        params = jax.tree_util.tree_map(
            lambda p, t: p - lr * t, params, trace)
        return params, trace

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    state = make_state()
    params, aux = state["params"], state["aux"]
    del state
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for step in range(steps):
            total, new_aux, shard_losses = None, None, []
            for i, shard in enumerate(shards):
                loss, aux_i, grads = grad_fn(params, aux, shard)
                shard_losses.append(loss)
                if i == 0:
                    new_aux = aux_i
                total = grads if total is None else add(total, grads)
                del grads
            params, trace = apply(params, trace, total)
            del total
            losses.append(
                sum(float(l) for l in shard_losses) / len(shards)
                if mean_loss else float(shard_losses[0]))
            aux = new_aux
            if step == 0:
                grad_norms = leaf_norms(trace)
    del trace
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": diff_norms({"params": params, "aux": aux},
                                       make_state())}


def compare(program: dict, reference: dict, limits: Dict[str, float]) -> dict:
    """``{number: {"value", "limit", "ok"}}`` for the three gaps."""
    values = {
        "loss_gap": loss_gap(program["losses"], reference["losses"]),
        "grad_norm_gap": worst_leaf_gap(program["grad_norms"],
                                        reference["grad_norms"]),
        "update_norm_gap": worst_leaf_gap(program["update_norms"],
                                          reference["update_norms"]),
    }
    return {k: {"value": v, "limit": limits[k], "ok": v <= limits[k]}
            for k, v in values.items()}
