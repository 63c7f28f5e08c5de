"""Priority dispatch across collective backends.

(reference: horovod/common/ops/operation_manager.{h,cc} — ordered op
lists, first ``Enabled()`` op wins, operation_manager.cc:32-60; the
priority order itself is set in ``CreateOperationManager``,
operations.cc:125-158: accelerator ops first, host fallbacks always
last.) Here the order is XLA-mesh (ICI/DCN) → TCP socket (host) →
local (size-1).
"""

from __future__ import annotations

from typing import List

from horovod_tpu.common import trace as htrace
from horovod_tpu.common.message import Response, ResponseType
from horovod_tpu.common.status import Status
from horovod_tpu.common.tensor_table import TensorTableEntry
from horovod_tpu.ops.backend import CollectiveBackend


class OperationManager:
    def __init__(self, backends: List[CollectiveBackend]):
        self._backends = backends
        self._metrics_on = False
        self._fusion_threshold_fn = None
        self._m_wall = {}

    def attach_metrics(self, registry, fusion_threshold_fn=None) -> None:
        """Install the per-op-type instrumentation the runtime's
        registry provides (the disabled registry hands back no-op
        metrics, keeping every call free): op counts, payload bytes
        per collective kind, collective wall-time histograms (issue
        time for async backends — completion rides the finalizer;
        observed by the ``hvd.execute`` span, not beside it), and the
        fusion-buffer fill ratio against the world threshold.
        Backends get their own per-plane counters via
        CollectiveBackend.attach_metrics."""
        from horovod_tpu.common.metrics import RATIO_BUCKETS
        self._metrics_on = bool(registry.enabled)
        self._fusion_threshold_fn = fusion_threshold_fn
        bytes_names = {
            ResponseType.ALLREDUCE: "hvd_bytes_allreduced_total",
            ResponseType.ALLGATHER: "hvd_bytes_allgathered_total",
            ResponseType.BROADCAST: "hvd_bytes_broadcast_total",
            ResponseType.ALLTOALL: "hvd_bytes_alltoall_total",
            ResponseType.REDUCESCATTER:
                "hvd_bytes_reducescattered_total",
        }
        self._m_ops = {}
        self._m_bytes = {}
        self._m_wall = {}
        for rt, bname in bytes_names.items():
            op = rt.name.lower()
            self._m_ops[rt] = registry.counter(
                f'hvd_ops_total{{op="{op}"}}')
            self._m_bytes[rt] = registry.counter(bname)
            self._m_wall[rt] = registry.histogram(
                f'hvd_collective_seconds{{op="{op}"}}',
                "collective execution wall time (issue time for "
                "async backends)")
        self._m_ops[ResponseType.BARRIER] = registry.counter(
            'hvd_ops_total{op="barrier"}')
        self._m_fill = registry.histogram(
            "hvd_fusion_fill_ratio",
            "fused batch bytes / fusion threshold", RATIO_BUCKETS)
        for b in self._backends:
            b.attach_metrics(registry)

    def attach_finalizer(self, finalizer) -> None:
        """Give every backend the runtime's Finalizer so it may return
        Status.InProgress and complete on a detached thread (reference:
        FinalizeCUDAQueue, cuda_operations.cc:148-179)."""
        for b in self._backends:
            b.finalizer = finalizer

    def attach_timeline(self, timeline) -> None:
        """Give every backend the rank-0 timeline so fusion memcpys show
        up as sub-activities (reference: mpi_operations.cc:35-62)."""
        for b in self._backends:
            b.timeline = timeline

    def close(self) -> None:
        """Release backend resources (ring channels, shm mappings) at
        shutdown."""
        for b in self._backends:
            close = getattr(b, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass

    def _pick(self, entries, response) -> CollectiveBackend:
        for b in self._backends:
            if b.enabled(entries, response):
                return b
        raise RuntimeError(
            f"No collective backend enabled for response "
            f"{response.response_type.name} ({response.tensor_names})")

    def pick(self, entries: List[TensorTableEntry],
             response: Response) -> CollectiveBackend:
        """The backend that WOULD execute this batch — the runtime's
        speculative fused cycle probes it (fused_cycle_reducible)
        before deciding to piggyback the payload on the negotiation
        round instead of dispatching here."""
        return self._pick(entries, response)

    def execute(self, entries: List[TensorTableEntry],
                response: Response) -> Status:
        """Run the batch on the first enabled backend, inside the
        program's ``hvd.execute`` span."""
        backend = self._pick(entries, response)
        rt = response.response_type
        # The span is the backend's call alone, the window
        # hvd_collective_seconds has always timed: counting comes first.
        sp = htrace.span("hvd.execute", n=len(entries),
                         also=self._m_wall.get(rt))
        if self._metrics_on or sp.on:
            nbytes = sum(getattr(e.tensor, "nbytes", 0) for e in entries)
            sp.nbytes = nbytes
            sp.tag = f"{rt.name.lower()}/{backend.name}"
            if self._metrics_on:
                self._count(backend, rt, len(entries), nbytes)
        with sp:
            return self._dispatch(backend, rt, entries, response)

    def _count(self, backend, rt, n_entries: int, nbytes: int) -> None:
        op_counter = self._m_ops.get(rt)
        if op_counter is not None:
            op_counter.inc()
        byte_counter = self._m_bytes.get(rt)
        if byte_counter is not None:
            byte_counter.inc(nbytes)
        backend.m_ops.inc()
        backend.m_bytes.inc(nbytes)
        if n_entries > 1 and self._fusion_threshold_fn is not None:
            threshold = self._fusion_threshold_fn()
            if threshold > 0:
                self._m_fill.observe(nbytes / threshold)

    @staticmethod
    def _dispatch(backend, rt, entries, response) -> Status:
        if rt == ResponseType.ALLREDUCE:
            return backend.execute_allreduce(entries, response)
        if rt == ResponseType.ALLGATHER:
            return backend.execute_allgather(entries, response)
        if rt == ResponseType.BROADCAST:
            return backend.execute_broadcast(entries, response)
        if rt == ResponseType.ALLTOALL:
            return backend.execute_alltoall(entries, response)
        if rt == ResponseType.REDUCESCATTER:
            return backend.execute_reducescatter(entries, response)
        if rt == ResponseType.BARRIER:
            return backend.execute_barrier(entries, response)
        raise ValueError(f"Cannot execute response type {rt}")
