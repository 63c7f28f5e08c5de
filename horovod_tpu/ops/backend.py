"""Collective backend interface.

Equivalent of the reference's ``HorovodOp`` hierarchy and its
``Enabled()`` protocol (reference: horovod/common/ops/
collective_operations.h:29-117): a backend reports whether it can run a
given batch of entries, and the OperationManager walks a priority list,
first enabled wins (reference: ops/operation_manager.cc:32-60).

Backends execute a whole (possibly fused) Response at once — the fusion
buffer pack/collective/unpack of the reference's
``MemcpyInFusionBuffer``/``MemcpyOutFusionBuffer``
(reference: ops/collective_operations.cc:35-63) happens inside
``execute_allreduce`` so each backend can fuse the way its transport
likes (numpy concatenation for the socket path, XLA concat/slice —
fused into the collective by the compiler — for the mesh path).
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import List

from horovod_tpu.common import trace as htrace
from horovod_tpu.common.message import Response
from horovod_tpu.common.metrics import NOOP_METRIC
from horovod_tpu.common.status import Status
from horovod_tpu.common.tensor_table import TensorTableEntry
from horovod_tpu.common.timeline import (
    ACT_MEMCPY_IN_FUSION_BUFFER, NOOP_TIMELINE,
)


class CollectiveBackend:
    name = "abstract"

    # Set by OperationManager.attach_finalizer when async completion is
    # enabled; backends that issue asynchronously submit a completion
    # closure and return Status.InProgress.
    finalizer = None

    # Set by OperationManager.attach_timeline (rank 0 with
    # HOROVOD_TIMELINE only); host planes wrap their fusion pack/unpack
    # in MEMCPY_IN/OUT_FUSION_BUFFER activities so timelines show where
    # fusion time goes (reference: mpi_operations.cc:35-62).
    timeline = NOOP_TIMELINE

    # Per-plane observability (common/metrics.py), installed by
    # OperationManager.attach_metrics; the class-attribute no-ops keep
    # unattached/disabled paths free. Subclasses may override
    # attach_metrics (calling super) to add plane-specific metrics.
    m_ops = NOOP_METRIC
    m_bytes = NOOP_METRIC

    def attach_metrics(self, registry) -> None:
        self.m_ops = registry.counter(
            f'hvd_backend_ops_total{{backend="{self.name}"}}',
            "collective batches executed by this data plane")
        self.m_bytes = registry.counter(
            f'hvd_backend_bytes_total{{backend="{self.name}"}}',
            "payload bytes moved through this data plane")

    @contextmanager
    def activity(self, names, act, enabled: bool = True, nbytes: int = 0):
        """A fusion pack or unpack: the program's ``hvd.pack`` /
        ``hvd.unpack`` span (yielded: a pack sets its ``nbytes`` once
        it has the buffer) and the timeline's sub-activity, which takes
        the span's clock readings. The finally guarantees both close
        even when the wrapped transport/pack raises, so an error
        mid-batch cannot misnest every later event in the trace."""
        if not enabled:
            yield htrace.NOOP_SPAN
            return
        sp = htrace.span("hvd.pack" if act == ACT_MEMCPY_IN_FUSION_BUFFER
                         else "hvd.unpack", nbytes=nbytes)
        sp.__enter__()
        self.timeline.activity_start_all(names, act, sp.start_ns)
        try:
            yield sp
        finally:
            sp.__exit__(None, None, None)
            self.timeline.activity_end_all(names, sp.end_ns)

    def enabled(self, entries: List[TensorTableEntry],
                response: Response) -> bool:
        raise NotImplementedError

    def fused_cycle_reducible(self, nbytes: int) -> bool:
        """True when a fused allreduce of ``nbytes`` would ride a
        star through the coordinator's control channels anyway — the
        precondition for the speculative fused cycle (runtime.py) to
        piggyback the payload on the negotiation round. Planes with
        their own transport (shm, ring, XLA mesh) say False so
        speculation never steals a batch from a faster data plane.
        ``nbytes`` is the batch's uncompressed input size, reckoned
        from the entries' metadata: the runtime asks before it
        converts or fetches any tensor, so the answer may depend on
        nothing else about the payloads."""
        return False

    def execute_allreduce(self, entries, response) -> Status:
        raise NotImplementedError

    def execute_allgather(self, entries, response) -> Status:
        raise NotImplementedError

    def execute_broadcast(self, entries, response) -> Status:
        raise NotImplementedError

    def execute_alltoall(self, entries, response) -> Status:
        raise NotImplementedError

    def execute_reducescatter(self, entries, response) -> Status:
        raise NotImplementedError

    def execute_barrier(self, entries, response) -> Status:
        return Status.OK()
