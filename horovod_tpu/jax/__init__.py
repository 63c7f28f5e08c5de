"""JAX framework adapter — the flagship user API.

Role-equivalent of the reference's per-framework adapters
(reference: horovod/tensorflow/__init__.py, horovod/torch/__init__.py):
basics re-exported, collective ops on framework tensors, an optimizer
wrapper that averages gradients across workers, and parameter/optimizer
state broadcast for checkpoint-restore symmetry (SURVEY §5
checkpoint/resume pattern).

Two gradient-sync paths, chosen by where your step runs:

- **in-jit (recommended on TPU)**: ``DistributedOptimizer(tx)`` wraps an
  optax GradientTransformation; inside a shard_map/pjit step it pmeans
  gradients over the mesh axis before the update — the role of the
  reference's DistributedOptimizer.compute_gradients override
  (reference: horovod/tensorflow/__init__.py:219-233), done where XLA
  can fuse and overlap it.
- **out-of-jit**: ``allreduce_gradients_async`` stages host gradients
  through the background runtime (negotiation, fusion, timeline — the
  full Horovod contract) — the role of torch's grad-hook + synchronize
  flow (reference: horovod/torch/__init__.py:95-147).
"""

from __future__ import annotations

from typing import Any

import numpy as np

# Basics + host-side ops (same surface as the reference adapters
# re-exporting HorovodBasics, reference: horovod/tensorflow/__init__.py:36-43)
from horovod_tpu.common.basics import (  # noqa: F401
    init, shutdown, initialized, rank, size, local_rank, local_size,
    cross_rank, cross_size, is_homogeneous,
)
from horovod_tpu.ops import (  # noqa: F401
    allreduce, allreduce_async, grouped_allreduce,
    grouped_allreduce_async, allgather, allgather_async,
    broadcast, broadcast_async, alltoall, alltoall_async,
    reducescatter, reducescatter_async, barrier, poll, synchronize,
    Average, Sum,
)
from horovod_tpu.common import basics as _basics
from horovod_tpu.common import trace as _htrace
from horovod_tpu.common.compression import Compression  # noqa: F401
from horovod_tpu import spmd as _spmd
from horovod_tpu.spmd import (  # noqa: F401
    zero_optimizer, zero_state_specs, sharded_clip_by_global_norm,
)


def DistributedOptimizer(tx, op: int = _spmd.Average,
                         axis="data", compression=Compression.none,
                         gradient_predivide_factor: float = 1.0):
    """Wrap an optax GradientTransformation so each ``update`` first
    averages gradients over the mesh ``axis`` (in-jit) — the optax
    rendering of the reference's DistributedOptimizer contract
    (reference: horovod/tensorflow/__init__.py:151-249). Use inside a
    shard_map/pjit-traced step with ``axis`` in scope; under a plain
    jit (GSPMD) you don't need it at all — replicated params + sharded
    batch already imply the gradient all-reduce. Give the step's
    ``jax.jit`` ``compiler_options=spmd.overlap_compiler_options(mesh,
    axis)`` so that on a TPU mesh of several chips the all-reduces run
    under the backward (docs/parallelism.md)."""
    import optax

    def init_fn(params):
        return tx.init(params)

    def update_fn(grads, state, params=None, **extra):
        import jax
        with jax.named_scope("exchange"):
            grads = exchange(grads)
        return tx.update(grads, state, params, **extra)

    def exchange(grads):
        if gradient_predivide_factor != 1.0 and op == _spmd.Average:
            # Reference semantics (horovod allreduce prescale/postscale):
            # prescale by 1/f before the sum, postscale by f/size after —
            # net effect is still the mean, but intermediate magnitudes
            # shrink for numerical headroom.
            f = gradient_predivide_factor

            def averaged(g):
                n = _spmd.mesh_size(axis)
                return _spmd.allreduce(g, op=_spmd.Sum, axis=axis,
                                       prescale_factor=1.0 / f,
                                       postscale_factor=f / n)

            import jax
            if compression is not Compression.none:
                def one(g):
                    c, ctx = compression.compress(g)
                    return compression.decompress(averaged(c), ctx)
            else:
                one = averaged
            return jax.tree_util.tree_map(one, grads)
        return _spmd.allreduce_gradients(grads, op=op, axis=axis,
                                         compression=compression)

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


def allreduce_gradients(grads, op: int = Average,
                        compression=Compression.none):
    """Synchronously average a host-side gradient pytree through the
    background runtime (negotiation + fusion + timeline) — the
    reference's hook-then-synchronize flow
    (reference: horovod/torch/__init__.py:95-147).

    The uncompressed path submits the leaves as ONE grouped
    allreduce, which the overlap tier (HOROVOD_OVERLAP_BUCKETS /
    HOROVOD_OVERLAP_BYTES, docs/performance.md Layer 5) splits into
    ready-order buckets: jax gradient leaves are futures, so early
    buckets negotiate and ride the wire while backward compute for
    later leaves is still running, and the tail ``synchronize`` drain
    only ever blocks on the last bucket."""
    import jax

    with _htrace.span("hvd.allreduce_gradients") as sp:
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        _count_leaves(sp, leaves)
        if compression is Compression.none:
            handles = grouped_allreduce_async(leaves, name="grad", op=op)
            outs = [synchronize(h) for h in handles]
        else:
            handles = []
            for i, g in enumerate(leaves):
                comp, ctx = compression.compress(g)
                handles.append(
                    (allreduce_async(comp, name=f"grad.{i}", op=op), ctx))
            outs = [compression.decompress(synchronize(h), ctx)
                    for h, ctx in handles]
        return _unflatten_done(sp, treedef, outs)


def _count_leaves(sp, leaves) -> None:
    """A whole tree's span counts its leaves and their bytes."""
    if sp.on:
        sp.n = len(leaves)
        sp.nbytes = sum(getattr(leaf, "nbytes", 0) for leaf in leaves)


def _unflatten_done(sp, treedef, outs):
    """The tree back, every handle done: its span takes the world
    cycle of the newest batch this rank began to execute."""
    import jax

    tree = jax.tree_util.tree_unflatten(treedef, outs)
    if sp.on:
        sp.cycle = _basics.active_runtime().exec_cycle
    return tree


def broadcast_parameters(params, root_rank: int = 0):
    """Broadcast a parameter pytree from ``root_rank`` through the
    runtime (reference: horovod/torch/__init__.py:200-229
    broadcast_parameters). Out-of-jit; for the in-jit form use
    horovod_tpu.spmd.broadcast_variables."""
    import jax

    with _htrace.span("hvd.broadcast_parameters") as sp:
        leaves, treedef = jax.tree_util.tree_flatten(params)
        _count_leaves(sp, leaves)
        handles = [broadcast_async(p, root_rank=root_rank,
                                   name=f"bcast.p.{i}")
                   for i, p in enumerate(leaves)]
        outs = [synchronize(h) for h in handles]
        return _unflatten_done(sp, treedef, outs)


def broadcast_optimizer_state(opt_state, root_rank: int = 0):
    """Broadcast optax optimizer state (an arbitrary pytree whose
    non-array leaves are left alone) — the reference's
    broadcast_optimizer_state incl. scalar wrapping
    (reference: horovod/torch/__init__.py:232-348)."""
    import jax

    def is_arr(x):
        return isinstance(x, (np.ndarray, np.generic)) or \
            type(x).__module__.startswith("jax")

    leaves, treedef = jax.tree_util.tree_flatten(opt_state)
    handles = []
    for i, leaf in enumerate(leaves):
        if is_arr(leaf):
            # 0-d arrays (step counters) broadcast like everything else —
            # the reference's scalar-wrapping dance is unnecessary here.
            handles.append(
                (i, broadcast_async(leaf, root_rank=root_rank,
                                    name=f"bcast.os.{i}")))
    out = list(leaves)
    for i, h in handles:
        res = synchronize(h)
        # preserve original leaf type/dtype for int steps
        orig = leaves[i]
        if isinstance(orig, np.ndarray):
            res = np.asarray(res, dtype=orig.dtype).reshape(orig.shape)
        out[i] = res
    return jax.tree_util.tree_unflatten(treedef, out)


def broadcast_train_state(state: Any, root_rank: int = 0):
    """Broadcast a whole train state (e.g. flax TrainState or the dicts
    produced by horovod_tpu.parallel.Trainer) from root_rank."""
    return broadcast_parameters(state, root_rank=root_rank)


__all__ = [
    "init", "shutdown", "initialized", "rank", "size", "local_rank",
    "local_size", "cross_rank", "cross_size", "is_homogeneous",
    "allreduce", "allreduce_async", "grouped_allreduce",
    "grouped_allreduce_async", "allgather", "allgather_async",
    "broadcast", "broadcast_async", "alltoall", "alltoall_async",
    "reducescatter", "reducescatter_async", "barrier", "poll",
    "synchronize", "Average", "Sum", "Compression",
    "DistributedOptimizer", "allreduce_gradients",
    "broadcast_parameters", "broadcast_optimizer_state",
    "broadcast_train_state", "zero_optimizer", "zero_state_specs",
    "sharded_clip_by_global_norm",
]
