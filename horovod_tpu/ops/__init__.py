"""Framework-neutral collective ops API (numpy / jax host tensors).

Equivalent of the reference's per-framework ``mpi_ops.py`` surfaces
(reference: horovod/torch/mpi_ops.py — sync + async + in-place variants,
handle map, poll/synchronize; horovod/tensorflow/mpi_ops.py), minus the
framework graph integration, which lives in horovod_tpu.jax / .torch.

Every op has a sync and an ``_async`` form returning an integer handle;
``poll`` / ``synchronize`` mirror the reference's handle protocol
(reference: horovod/torch/handle_manager.h:31-42). Auto-generated names
use per-op counters, which agree across ranks as long as ops are created
in the same order — same contract as the reference's
``allreduce.noname.<n>`` naming.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

from horovod_tpu.common import basics
from horovod_tpu.common import lockdep
from horovod_tpu.common import trace as htrace
from horovod_tpu.common.message import (
    RequestType, numpy_dtype_to_datatype,
)
from horovod_tpu.common.status import (
    HorovodInternalError, Status, WorldAbortedError,
)
from horovod_tpu.common.tensor_table import TensorTableEntry

# Reduction op constants (modern-horovod compatible; the reference's
# `average=True` flag maps onto these).
Average = 0
Sum = 1

_counter_lock = lockdep.lock("ops._counter_lock")
# (scope, kind) -> count. The scope is the active runtime's tenant
# name ('' = default world): each tenant's auto-name sequence must be
# a pure function of ITS OWN submission order — keyed globally, two
# tenants interleaving differently per process would diverge names
# across ranks.
_counters = {}


def _auto_name(kind: str) -> str:
    scope = basics.active_scope()
    with _counter_lock:
        n = _counters.get((scope, kind), 0)
        _counters[(scope, kind)] = n + 1
    return f"{kind}.noname.{n}"


def reset_name_counters(scope=None) -> None:
    """Called by init()/create_tenant so re-initialized worlds agree
    on auto names. ``scope`` clears one world's counters (''=default,
    a tenant name otherwise); None clears everything."""
    with _counter_lock:
        if scope is None:
            _counters.clear()
        else:
            for key in [k for k in _counters if k[0] == scope]:
                del _counters[key]


def _inspect(tensor):
    """-> (payload, context, device, np_dtype, shape, ready_fn)"""
    if isinstance(tensor, np.ndarray) or np.isscalar(tensor):
        arr = np.asarray(tensor)
        return arr, None, -1, arr.dtype, arr.shape, None
    # duck-type jax arrays without importing jax eagerly
    mod = type(tensor).__module__
    if mod.startswith("jax") or hasattr(tensor, "addressable_shards"):
        try:
            dev = sorted(d.id for d in tensor.devices())[0]
        except Exception:
            dev = 0
        # No ready_fn: jax arrays are futures — backends order on the
        # producing computation via their own consumption (np.asarray /
        # device_put), so no ReadyEvent poll is needed (and is_ready()
        # off-thread is pathologically slow on some platforms).
        return (tensor, "jax", dev, np.dtype(tensor.dtype),
                tuple(tensor.shape), None)
    arr = np.asarray(tensor)
    return arr, None, -1, arr.dtype, arr.shape, None


def _enqueue(kind: RequestType, tensor, name: Optional[str],
             root_rank: int = -1, prescale: float = 1.0,
             postscale: float = 1.0) -> int:
    rt = basics.active_runtime()
    with htrace.span("hvd.enqueue", n=1):
        payload, ctx, device, np_dtype, shape, ready_fn = _inspect(tensor)
        dtype = numpy_dtype_to_datatype(np_dtype)
        name = name or _auto_name(kind.name.lower())
        handle = rt.handle_manager.allocate()

        entry = TensorTableEntry(tensor_name=name, tensor=payload,
                                 root_rank=root_rank, device=device,
                                 ready_fn=ready_fn, context=ctx)

        def callback(status: Status) -> None:
            rt.handle_manager.mark_done(handle, status, entry.output)

        entry.callback = callback
        status = rt.enqueue(kind, entry, dtype, shape, prescale, postscale)
        if not status.ok():
            rt.handle_manager.mark_done(handle, status, None)
    return handle


def poll(handle: int) -> bool:
    """True when the op behind ``handle`` has completed
    (reference: horovod/torch/mpi_ops.py poll)."""
    return basics.active_runtime().handle_manager.poll(handle)


def synchronize(handle: int) -> Any:
    """Block until completion; raise on error; return the output tensor
    (reference: horovod/torch/mpi_ops.py synchronize + WaitAndClear).
    A fail-fast world abort surfaces as WorldAbortedError (a
    HorovodInternalError subclass) carrying the originating rank."""
    rt = basics.active_runtime()
    try:
        with htrace.span("hvd.synchronize") as sp:
            status = rt.handle_manager.wait(handle)
            sp.cycle = rt.exec_cycle
    except ValueError:
        # Handle ids are unique across world generations, so a stale
        # id is provably from BEFORE an elastic resize (its collective
        # already completed with WorldAbortedError when the old world
        # tore down) — say so. Current-generation misuse (double
        # synchronize, garbage id) keeps the plain ValueError.
        if not rt.handle_manager.from_prior_generation(handle):
            raise
        raise HorovodInternalError(
            f"handle {handle} belongs to a previous world generation: "
            f"async handles do not survive an elastic resize — their "
            f"collectives failed with WorldAbortedError at the abort; "
            f"re-enqueue after recovery") from None
    output = rt.handle_manager.release(handle)
    if not status.ok():
        if status.aborted_by is not None:
            raise WorldAbortedError(status.reason,
                                    origin_rank=status.aborted_by)
        raise HorovodInternalError(status.reason)
    return output


# -- allreduce -----------------------------------------------------------
def _check_scalable_dtype(tensor, op, prescale, postscale, opname):
    """Integer tensors cannot be averaged or scaled — the factor would be
    truncated to 0 in the tensor dtype, silently corrupting results."""
    kind = np.dtype(tensor.dtype).kind if hasattr(tensor, "dtype") \
        else np.asarray(tensor).dtype.kind
    if kind in "iub" and (op == Average or prescale != 1.0
                          or postscale != 1.0):
        raise ValueError(
            f"Averaging or scaling during {opname} is not supported for "
            "integer tensors; use op=Sum with unit scale factors.")


def allreduce_async(tensor, average: Optional[bool] = None,
                    name: Optional[str] = None, op: Optional[int] = None,
                    prescale_factor: float = 1.0,
                    postscale_factor: float = 1.0) -> int:
    """Sum (or average) ``tensor`` across ranks
    (reference: horovod/torch/mpi_ops.py allreduce_async,
    horovod/tensorflow/__init__.py:46-92)."""
    if average is None and op is None:
        op = Average
    elif op is None:
        op = Average if average else Sum
    _check_scalable_dtype(tensor, op, prescale_factor, postscale_factor,
                          "allreduce")
    if op == Average:
        postscale_factor = postscale_factor / basics.size()
    return _enqueue(RequestType.ALLREDUCE, tensor, name,
                    prescale=prescale_factor, postscale=postscale_factor)


def allreduce(tensor, average: Optional[bool] = None,
              name: Optional[str] = None, op: Optional[int] = None,
              prescale_factor: float = 1.0,
              postscale_factor: float = 1.0) -> Any:
    return synchronize(allreduce_async(tensor, average, name, op,
                                       prescale_factor, postscale_factor))


def grouped_allreduce_async(tensors, average: Optional[bool] = None,
                            name: Optional[str] = None,
                            op: Optional[int] = None,
                            prescale_factor: float = 1.0,
                            postscale_factor: float = 1.0) -> list:
    """Submit a list of tensors as one logical allreduce group under
    derived names ``{name}.<i>`` (later-Horovod ``grouped_allreduce``
    surface; the reference's coordinator batches implicitly via
    fusion — horovod/common/operations.cc:1118-1234). Returns one
    handle per tensor.

    Atomicity is guaranteed, not best-effort: all members enter the
    negotiation in ONE RequestList (Runtime.enqueue_group holds the
    table lock across the whole insert), so a concurrent cycle tick or
    another submitting thread can never split the group — compatible
    members under the fusion threshold land in one fused Response.

    Every member is VALIDATED before any member is enqueued, so a bad
    tensor (unsupported dtype, unscalable integer average) fails the
    whole call without leaking half a group in flight — peers never
    block on members this rank never submitted."""
    with htrace.span("hvd.enqueue", n=len(tensors)):
        return _grouped_allreduce_async(tensors, average, name, op,
                                        prescale_factor, postscale_factor)


def _grouped_allreduce_async(tensors, average, name, op, prescale_factor,
                             postscale_factor) -> list:
    if name is None:
        name = _auto_name("grouped_allreduce")
    resolved_op = op if op is not None else (
        Average if (average is None or average) else Sum)
    post = postscale_factor
    if resolved_op == Average:
        post = post / basics.size()

    # Scaling only matters under Average/non-unit factors — hoisting
    # the gate keeps the steady Sum path (DDP-style gradient buckets)
    # from paying a per-tensor dtype probe.
    check_scale = (resolved_op == Average or prescale_factor != 1.0
                   or postscale_factor != 1.0)
    inspected = []
    nbytes_list = []
    for t in tensors:
        # Unsupported payloads AND unsupported dtypes must raise before
        # any enqueue — numpy_dtype_to_datatype is what the enqueue
        # would reject later, so run it here too (e.g. complex64).
        payload, ctx, device, np_dtype, shape, ready_fn = _inspect(t)
        dtype = numpy_dtype_to_datatype(np_dtype)
        if check_scale:
            _check_scalable_dtype(t, resolved_op, prescale_factor,
                                  postscale_factor, "grouped_allreduce")
        inspected.append((payload, ctx, device, dtype, shape, ready_fn))
        numel = 1
        for d in shape:
            numel *= int(d)
        nbytes_list.append(numel * np_dtype.itemsize)

    rt = basics.active_runtime()
    mark_done = rt.handle_manager.mark_done
    handles = rt.handle_manager.allocate_many(len(inspected))
    items = []
    for i, (payload, ctx, device, dtype, shape,
            ready_fn) in enumerate(inspected):
        entry = TensorTableEntry(tensor_name=f"{name}.{i}",
                                 tensor=payload, root_rank=-1,
                                 device=device, ready_fn=ready_fn,
                                 context=ctx)

        def callback(status, entry=entry, handle=handles[i]):
            mark_done(handle, status, entry.output)

        entry.callback = callback
        items.append((entry, dtype, shape))

    # Overlap tier (HOROVOD_OVERLAP_BUCKETS/_BYTES, docs/performance.md
    # Layer 5): split the group into size-balanced CONTIGUOUS buckets,
    # each enqueued as its OWN atomic negotiation batch — early buckets
    # negotiate and reduce while the caller's later gradients are still
    # materializing (jax leaves are futures: the data plane's
    # np.asarray blocks per bucket, so dispatch follows readiness).
    # Tensor names are identical either way, so bucketing never changes
    # numerics — only the fused-batch boundaries.
    bucket_ends = rt.overlap_bucket_plan(nbytes_list)
    if bucket_ends is None:
        # With the overlap runner armed, every grouped call is itself
        # a dispatch unit: record its name set so the background loop
        # peels multi-group pops at group boundaries and each group
        # rides its own in-flight cycle (callers doing their own
        # ready-order bucketing get pipelining without the splitter).
        rt.note_bucket_names(
            entry.tensor_name for entry, _d, _s in items)
        status = rt.enqueue_group(RequestType.ALLREDUCE, items,
                                  prescale_factor, post)
        if not status.ok():
            # Nothing was inserted (all-or-nothing): fail every handle.
            for h in handles:
                rt.handle_manager.mark_done(h, status, None)
        return handles
    rt.note_overlap_buckets(len(bucket_ends))
    start = 0
    for end in bucket_ends:
        rt.note_bucket_names(
            entry.tensor_name for entry, _d, _s in items[start:end])
        status = rt.enqueue_group(RequestType.ALLREDUCE,
                                  items[start:end],
                                  prescale_factor, post)
        if not status.ok():
            # All-or-nothing PER BUCKET: earlier buckets are already
            # in flight (peers expect them); fail this bucket's
            # handles and keep submitting the rest so the world stays
            # in lockstep on every other bucket.
            for h in handles[start:end]:
                rt.handle_manager.mark_done(h, status, None)
        start = end
    return handles


def grouped_allreduce(tensors, average: Optional[bool] = None,
                      name: Optional[str] = None,
                      op: Optional[int] = None,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0) -> list:
    """Blocking grouped allreduce with all-or-nothing error semantics:
    every member handle is drained even when one fails, then the first
    error raises — no member is left silently in flight."""
    handles = grouped_allreduce_async(tensors, average, name, op,
                                      prescale_factor, postscale_factor)
    outs, first_error = [], None
    for h in handles:
        try:
            outs.append(synchronize(h))
        except HorovodInternalError as e:
            outs.append(None)
            if first_error is None:
                first_error = e
    if first_error is not None:
        raise first_error
    return outs


# -- allgather -----------------------------------------------------------
def allgather_async(tensor, name: Optional[str] = None) -> int:
    """Concatenate each rank's tensor along dim 0; dim 0 may differ per
    rank (reference: horovod/common/ops/mpi_operations.cc:95-173
    MPI_Allgatherv semantics)."""
    return _enqueue(RequestType.ALLGATHER, tensor, name)


def allgather(tensor, name: Optional[str] = None) -> Any:
    return synchronize(allgather_async(tensor, name))


def allgather_grad(grad, local_d0: int, name: str) -> np.ndarray:
    """Backward of a named allgather, shared by the framework adapters
    (reference gradient: HorovodAllgather, horovod/torch/mpi_ops.py:
    236-254 and tensorflow/mpi_ops.py:127-148): sum-allreduce the
    upstream gradient of the CONCATENATED output, then keep this
    rank's dim-0 slice, located via an allgather of the per-rank
    sizes (variable dim-0 supported). ``name`` must be the forward's
    resolved op name — the derived grad-op names stay deterministic
    across ranks regardless of backward execution order."""
    sizes = np.asarray(allgather(np.asarray([local_d0], np.int64),
                                 name=f"{name}.grad.sizes"))
    summed = np.asarray(allreduce(np.asarray(grad), op=Sum,
                                  name=f"{name}.grad"))
    off = int(sizes[:basics.rank()].sum())
    return summed[off:off + local_d0]


# -- broadcast -----------------------------------------------------------
def broadcast_async(tensor, root_rank: int,
                    name: Optional[str] = None) -> int:
    return _enqueue(RequestType.BROADCAST, tensor, name,
                    root_rank=root_rank)


def broadcast(tensor, root_rank: int, name: Optional[str] = None) -> Any:
    return synchronize(broadcast_async(tensor, root_rank, name))


# -- alltoall (TPU-native extension) -------------------------------------
def alltoall_async(tensor, name: Optional[str] = None) -> int:
    """Scatter dim-0 blocks to every rank and gather their blocks back;
    requires dim 0 divisible by size."""
    return _enqueue(RequestType.ALLTOALL, tensor, name)


def alltoall(tensor, name: Optional[str] = None) -> Any:
    return synchronize(alltoall_async(tensor, name))


# -- reducescatter (TPU-native extension) --------------------------------
def reducescatter_async(tensor, name: Optional[str] = None,
                        op: int = Sum) -> int:
    _check_scalable_dtype(tensor, op, 1.0, 1.0, "reducescatter")
    postscale = 1.0 / basics.size() if op == Average else 1.0
    return _enqueue(RequestType.REDUCESCATTER, tensor, name,
                    postscale=postscale)


def reducescatter(tensor, name: Optional[str] = None, op: int = Sum) -> Any:
    return synchronize(reducescatter_async(tensor, name, op))


# -- barrier -------------------------------------------------------------
def barrier(name: Optional[str] = None) -> None:
    """Block until every rank reaches the barrier."""
    handle = _enqueue(RequestType.BARRIER,
                      np.zeros((), np.uint8), name or _auto_name("barrier"))
    synchronize(handle)
