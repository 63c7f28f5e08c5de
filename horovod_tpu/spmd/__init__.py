"""In-jit SPMD collectives over a device mesh — the TPU-first data plane.

This is the idiomatic-TPU half of the framework. The reference reaches
its collectives from *outside* the step function: the trainer produces a
gradient, then hands it to a background runtime that negotiates and runs
NCCL/MPI (reference: horovod/common/operations.cc RunLoopOnce +
horovod/common/ops/nccl_operations.cc). On TPU the profitable design is
the inverse: collectives live *inside* the jitted step, where XLA can
fuse them with the surrounding compute, overlap them with the backward
pass, and schedule them onto ICI directly. This module provides that
surface with the same op vocabulary as the host-side API (allreduce /
allgather / broadcast / alltoall / reducescatter — reference:
horovod/torch/mpi_ops.py), as ``jax.lax`` wrappers keyed by mesh axis
names instead of communicator handles.

Hierarchy note: the reference's hierarchical allreduce (local
reduce-scatter → cross-node allreduce → local allgather, reference:
horovod/common/ops/nccl_operations.cc:167-372) is expressed here by
factoring the mesh into ('dcn', 'ici') axes and passing both to
``psum`` — XLA decomposes the reduction per axis, riding ICI
intra-slice and DCN across slices.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple, Union

Average = 0
Sum = 1
Min = 2
Max = 3

AxisName = Union[str, Tuple[str, ...]]


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------

def create_mesh(axes: Optional[Dict[str, int]] = None,
                devices=None,
                allow_split_physical_axes: bool = False):
    """Build a ``jax.sharding.Mesh``.

    ``axes`` maps axis name → size; at most one size may be ``-1``
    (filled with the remaining devices). Default: one ``'data'`` axis
    over every visible device — the mesh equivalent of the reference's
    MPI_COMM_WORLD (reference: horovod/common/operations.cc:695-727).

    On multi-host platforms the device order comes from
    ``mesh_utils.create_device_mesh`` so that the trailing axes map to
    ICI neighbours and leading axes to DCN, mirroring the reference's
    LOCAL/CROSS communicator split
    (reference: horovod/common/operations.cc:729-764).
    """
    from horovod_tpu.compat import jaxshim
    return jaxshim.make_mesh(
        axes, devices=devices,
        allow_split_physical_axes=allow_split_physical_axes)


def create_hybrid_mesh(ici_axes: Dict[str, int],
                       dcn_axes: Dict[str, int]):
    """Two-level mesh for multi-slice jobs: ``dcn_axes`` shard across
    slices (data-center network), ``ici_axes`` within a slice. The
    TPU-native form of the reference's is_homogeneous + LOCAL/CROSS
    communicator machinery (reference: horovod/common/operations.cc:
    729-764, mpi_context.h GetMPICommunicator)."""
    from horovod_tpu.compat import jaxshim
    return jaxshim.make_hybrid_mesh(ici_axes, dcn_axes)


def mesh_rank(axis: AxisName = "data"):
    """In-jit rank along ``axis`` (reference: horovod_rank,
    horovod/common/operations.cc:1377-1383 — but per-axis)."""
    import jax

    from horovod_tpu.compat import jaxshim
    if isinstance(axis, (tuple, list)):
        import jax.numpy as jnp
        r = jnp.int32(0)
        for a in axis:
            r = r * jaxshim.axis_size(a) + jax.lax.axis_index(a)
        return r
    return jax.lax.axis_index(axis)


def mesh_size(axis: AxisName = "data") -> int:
    from horovod_tpu.compat import jaxshim
    if isinstance(axis, (tuple, list)):
        return math.prod(jaxshim.axis_size(a) for a in axis)
    return jaxshim.axis_size(axis)


# ---------------------------------------------------------------------------
# Collectives (usable inside shard_map/pjit with the named axes in scope)
# ---------------------------------------------------------------------------

def allreduce(x, op: int = Average, axis: AxisName = "data",
              prescale_factor: float = 1.0, postscale_factor: float = 1.0):
    """Cross-replica reduction. ``Average`` divides by the axis size —
    the reference's ``average=True`` contract
    (reference: horovod/tensorflow/__init__.py:46-92)."""
    import jax
    import jax.numpy as jnp
    if prescale_factor != 1.0:
        x = x * jnp.asarray(prescale_factor, x.dtype)
    if op == Average:
        y = jax.lax.pmean(x, axis)
    elif op == Sum:
        y = jax.lax.psum(x, axis)
    elif op == Min:
        y = jax.lax.pmin(x, axis)
    elif op == Max:
        y = jax.lax.pmax(x, axis)
    else:
        raise ValueError(f"unknown reduction op {op}")
    if postscale_factor != 1.0:
        y = y * jnp.asarray(postscale_factor, y.dtype)
    return y


def allgather(x, axis: AxisName = "data"):
    """Concatenate each replica's tensor along dim 0
    (reference allgather semantics: variable dim-0 concat,
    horovod/common/ops/mpi_operations.cc:95-173; inside jit all shards
    are necessarily the same shape — variable dim-0 lives on the
    host-side path in horovod_tpu.ops)."""
    import jax
    return jax.lax.all_gather(x, axis, tiled=True)


def broadcast(x, root_rank: int = 0, axis: AxisName = "data"):
    """Every replica receives root's value. Masked-psum formulation —
    branchless, fusable, and correct for any dtype castable to itself
    (reference: horovod/common/ops/mpi_operations.cc:334-358)."""
    import jax
    import jax.numpy as jnp
    idx = mesh_rank(axis)
    contrib = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
    return jax.lax.psum(contrib, axis)


def alltoall(x, axis: AxisName = "data"):
    """Tiled all-to-all over dim 0: row-block d of the result came from
    replica d. Matches the host-side alltoall block semantics."""
    import jax
    return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=True)


def reducescatter(x, op: int = Average, axis: AxisName = "data"):
    """Reduce then keep this replica's dim-0 shard
    (reference: the reduce-scatter stage of NCCLHierarchicalAllreduce,
    horovod/common/ops/nccl_operations.cc:222-236)."""
    from horovod_tpu.compat import jaxshim
    if op not in (Average, Sum):
        raise ValueError("reducescatter supports Average/Sum only "
                         f"(got op={op}); XLA's reduce-scatter is a sum")
    y = jaxshim.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    if op == Average:
        y = y / mesh_size(axis)
    return y


# ---------------------------------------------------------------------------
# Gradient helpers (pytree versions, the DistributedOptimizer hot path)
# ---------------------------------------------------------------------------

def allreduce_gradients(grads, op: int = Average, axis: AxisName = "data",
                        compression=None):
    """Cross-replica (mean) reduction of a gradient pytree. With
    ``compression`` (horovod_tpu.Compression.fp16 / .bf16), gradients
    are cast down before the reduction and restored after — the wire
    compression contract (reference: horovod/tensorflow/compression.py:
    46-64) realized as a cast around psum so XLA fuses it into the
    collective's pack/unpack."""
    import jax

    def one(g):
        if compression is not None:
            g, ctx = compression.compress(g)
            r = allreduce(g, op=op, axis=axis)
            return compression.decompress(r, ctx)
        return allreduce(g, op=op, axis=axis)

    return jax.tree_util.tree_map(one, grads)


def broadcast_variables(tree, root_rank: int = 0, axis: AxisName = "data"):
    """Broadcast a pytree of arrays from ``root_rank`` — in-jit form of
    the reference's broadcast_parameters / BroadcastGlobalVariablesHook
    (reference: horovod/torch/__init__.py:200-229,
    horovod/tensorflow/__init__.py:95-148)."""
    import jax
    return jax.tree_util.tree_map(
        lambda t: broadcast(t, root_rank=root_rank, axis=axis), tree)


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------

def batch_sharding(mesh, axis: AxisName = "data"):
    """NamedSharding that splits dim 0 across ``axis`` (the global-batch
    layout for data parallelism)."""
    from horovod_tpu.compat import jaxshim
    return jaxshim.named_sharding(mesh, jaxshim.partition_spec(axis))

def replicated_sharding(mesh):
    from horovod_tpu.compat import jaxshim
    return jaxshim.named_sharding(mesh, jaxshim.partition_spec())


def shard_batch(mesh, batch, axis: AxisName = "data"):
    """Place a host batch (pytree of arrays) onto the mesh, dim 0 split
    across ``axis``."""
    import jax
    sh = batch_sharding(mesh, axis)
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), batch)


# Imported last: zero.py uses the mesh helpers defined above.
from horovod_tpu.spmd.zero import (  # noqa: E402
    zero_optimizer, zero_state_specs, sharded_clip_by_global_norm,
)
from horovod_tpu.spmd.overlap import (  # noqa: E402
    overlap_compiler_options, collective_schedule,
)
from horovod_tpu.spmd.scopes import (  # noqa: E402
    device_scopes, scope_of, note_compiled, noted_device_scopes,
)

__all__ = [
    "Average", "Sum", "Min", "Max",
    "create_mesh", "create_hybrid_mesh", "mesh_rank", "mesh_size",
    "allreduce", "allgather", "broadcast", "alltoall", "reducescatter",
    "allreduce_gradients", "broadcast_variables",
    "batch_sharding", "replicated_sharding", "shard_batch",
    "zero_optimizer", "zero_state_specs", "sharded_clip_by_global_norm",
    "overlap_compiler_options", "collective_schedule",
    "device_scopes", "scope_of", "note_compiled", "noted_device_scopes",
]
