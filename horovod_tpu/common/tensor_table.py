"""Tensor table, message queue and handle manager.

The tensor table holds the per-process payloads of in-flight collectives,
keyed by name, while the message queue carries the matching Requests to
the background loop (reference: horovod/common/global_state.h:48-57 and
common.h:165-184 ``TensorTableEntry``/``TensorTable``). Handles mirror
the torch binding's ``HandleManager`` (reference:
horovod/torch/handle_manager.h:31-42) and are used by every async API.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional

from horovod_tpu.common import lockdep
from horovod_tpu.common.message import Request
from horovod_tpu.common.status import Status


class TensorTableEntry:
    """One in-flight collective on this process
    (reference: common.h:165-182)."""

    __slots__ = ("tensor_name", "tensor", "output", "root_rank", "device",
                 "callback", "ready_fn", "request_type", "context")

    def __init__(self, tensor_name: str, tensor: Any,
                 root_rank: int = -1, device: int = -1,
                 callback: Optional[Callable[[Status], None]] = None,
                 ready_fn: Optional[Callable[[], bool]] = None,
                 request_type=None, context: Any = None):
        self.tensor_name = tensor_name
        self.tensor = tensor          # input payload (numpy or jax array)
        self.output = None            # set by the executing backend
        self.root_rank = root_rank
        self.device = device
        self.callback = callback
        self.ready_fn = ready_fn      # None => ready immediately
        self.request_type = request_type
        self.context = context        # adapter-specific opaque state


class TensorTable:
    """Name-keyed table of pending entries + the per-cycle message queue,
    guarded by one mutex like the reference's
    (reference: operations.cc:1455 mutex usage)."""

    def __init__(self):
        self._lock = lockdep.lock("tensor_table.TensorTable._lock")
        self._table: Dict[str, TensorTableEntry] = {}
        self._message_queue: List[Request] = []
        # When the queue's oldest request was added, as the adder
        # stamped it (``now_ns``; 0 = not stamped: tracing is off), and
        # the same for the batch pop_messages last drained: the two
        # ends of the program's hvd.queue_wait interval.
        self._queued_ns = 0
        self.popped_queued_ns = 0

    def add(self, entry: TensorTableEntry, request: Request,
            now_ns: int = 0) -> bool:
        """Insert entry + request atomically. Returns False on duplicate
        name (reference: operations.cc:1459-1462 DUPLICATE_NAME_ERROR)."""
        with self._lock:
            if entry.tensor_name in self._table:
                return False
            self._table[entry.tensor_name] = entry
            self._message_queue.append(request)
            if not self._queued_ns:
                self._queued_ns = now_ns
            return True

    def add_all(self, pairs, now_ns: int = 0) -> Optional[str]:
        """Insert several (entry, request) pairs under ONE lock hold —
        all-or-nothing, and atomic w.r.t. pop_messages, so a concurrent
        cycle tick can never split the batch across two RequestLists
        (the grouped-allreduce atomicity contract). Returns the first
        duplicate name, or None on success."""
        with self._lock:
            for entry, _ in pairs:
                if entry.tensor_name in self._table:
                    return entry.tensor_name
            for entry, request in pairs:
                self._table[entry.tensor_name] = entry
                self._message_queue.append(request)
            if not self._queued_ns:
                self._queued_ns = now_ns
            return None

    def pop_messages(self) -> List[Request]:
        """Drain the message queue for this cycle
        (reference: operations.cc:1000-1012)."""
        with self._lock:
            msgs = self._message_queue
            self._message_queue = []
            self.popped_queued_ns, self._queued_ns = self._queued_ns, 0
            return msgs

    def requeue(self, requests: List[Request]) -> None:
        """Return popped requests to the FRONT of the message queue, in
        order (negotiation fast path: a cache hit the world did not
        grant this cycle stays pending and rides the next cycle's
        bitmask). Requests whose entry vanished meanwhile (shutdown
        fan-out reclaimed it) are dropped — resurrecting them would
        complete a handle twice."""
        with self._lock:
            live = [r for r in requests if r.tensor_name in self._table]
            if live:
                self._message_queue[:0] = live

    def queue_pending(self) -> bool:
        """True if any request is waiting for the next cycle (new
        submissions or fast-path requeues) — the cycle loop's signal
        that it must start another negotiation round immediately."""
        with self._lock:
            return bool(self._message_queue)

    def pop_entry(self, name: str) -> TensorTableEntry:
        with self._lock:
            return self._table.pop(name)

    def peek_entries(self, names):
        """The entries for ``names`` WITHOUT removing them, or None if
        any is absent — the speculative fused cycle packs its payload
        from live entries but must not consume them until the world
        confirms the grant (a mispredicted cycle falls back to the
        classic path, which pops them itself)."""
        with self._lock:
            table = self._table
            try:
                return [table[n] for n in names]
            except KeyError:
                return None

    def pop_entries(self, names) -> List[TensorTableEntry]:
        """Remove and return the present entries among ``names`` under
        ONE lock acquisition — a fused response's per-entry get/pop
        pairs are a measurable share of the execution hot path."""
        with self._lock:
            table = self._table
            return [table.pop(n) for n in names if n in table]

    def pop_entry_if_present(self, name: str):
        with self._lock:
            self._message_queue = [m for m in self._message_queue
                                   if m.tensor_name != name]
            return self._table.pop(name, None)

    def get_entry(self, name: str) -> Optional[TensorTableEntry]:
        with self._lock:
            return self._table.get(name)

    def pop_all(self) -> List[TensorTableEntry]:
        """Remove and return every pending entry (shutdown fan-out,
        reference: operations.cc:898-913)."""
        with self._lock:
            entries = list(self._table.values())
            self._table.clear()
            self._message_queue = []
            return entries

    def __len__(self):
        with self._lock:
            return len(self._table)


# Process-lifetime handle watermark: an elastic resize
# (common/elastic.py) replaces the Runtime — and with it the
# HandleManager — while user code may still hold handles from the old
# world. Restarting ids at 0 would let a stale handle COLLIDE with a
# fresh one and silently return the wrong tensor; continuing from the
# watermark makes a stale handle an unambiguous "Invalid handle"
# instead. Only one live manager allocates at a time (the old
# runtime is torn down before the new one starts), so the plain
# module global needs no lock of its own.
_HANDLE_WATERMARK = 0


class HandleManager:
    """Integer handles for async ops; poll/wait on completion status
    (reference: horovod/torch/handle_manager.{h,cc}). Ids are unique
    across every manager the process ever creates (elastic resizes
    create a new one per world generation — see _HANDLE_WATERMARK)."""

    def __init__(self):
        self._lock = lockdep.lock("tensor_table.HandleManager._lock")
        self._cv = threading.Condition(self._lock)
        self._base = _HANDLE_WATERMARK  # ids at or below: prior manager
        self._last = _HANDLE_WATERMARK
        self._waiters = 0
        self._results: Dict[int, Optional[Status]] = {}
        self._outputs: Dict[int, Any] = {}

    def from_prior_generation(self, handle: int) -> bool:
        """True when ``handle`` was allocated by a manager that
        predates this one (an elastic resize replaced the runtime):
        its collective completed — with WorldAbortedError — before
        the old world tore down. Distinguishes that case from
        current-world misuse (double release, garbage id)."""
        return 0 < handle <= self._base

    def allocate(self) -> int:
        global _HANDLE_WATERMARK
        with self._lock:
            self._last += 1
            handle = self._last
            _HANDLE_WATERMARK = self._last
            self._results[handle] = None
            return handle

    def allocate_many(self, n: int) -> List[int]:
        """``n`` fresh handles under ONE lock acquisition — a grouped
        submission's per-handle locking is a measurable share of the
        steady-state submit path."""
        global _HANDLE_WATERMARK
        with self._lock:
            first = self._last + 1
            self._last += n
            _HANDLE_WATERMARK = self._last
            handles = list(range(first, self._last + 1))
            for h in handles:
                self._results[h] = None
            return handles

    def poll(self, handle: int) -> bool:
        with self._lock:
            if handle not in self._results:
                raise ValueError(f"Invalid handle {handle}")
            return self._results[handle] is not None

    def mark_done(self, handle: int, status: Status,
                  output: Any = None) -> None:
        with self._cv:
            # Output BEFORE status: wait()'s lock-free fast path keys
            # on a non-None status, so the status store must publish
            # last or a racing synchronize() could release a handle
            # whose output was not yet visible.
            self._outputs[handle] = output
            self._results[handle] = status
            # A fused batch completes its handles in one burst while
            # the app waits on at most a few of them — the wake-up is
            # only worth paying when somebody is actually blocked.
            if self._waiters:
                self._cv.notify_all()

    _MISSING = object()

    def wait(self, handle: int, timeout: Optional[float] = None) -> Status:
        # Lock-free fast path: dict reads are atomic under the GIL and
        # mark_done stores the final Status in one assignment, so a
        # completed handle (the common case when draining a fused
        # batch: the first wait blocks, the rest are already done)
        # never pays the condition-variable lock.
        res = self._results.get(handle, self._MISSING)
        if res is self._MISSING:
            raise ValueError(f"Invalid handle {handle}")
        if res is not None:
            return res
        with self._cv:
            if self._results[handle] is not None:
                return self._results[handle]
            self._waiters += 1
            try:
                ok = self._cv.wait_for(
                    lambda: self._results[handle] is not None, timeout)
            finally:
                self._waiters -= 1
            if not ok:
                raise TimeoutError(
                    f"Timed out waiting for handle {handle}")
            return self._results[handle]

    def release(self, handle: int) -> Any:
        """Return the output and clear the handle
        (reference: handle_manager.cc ReleaseHandle/WaitAndClear).
        Lockless: dict pops are GIL-atomic and a handle is released by
        exactly one caller, after completion — no invariant spans the
        two pops."""
        out = self._outputs.pop(handle, None)
        self._results.pop(handle, None)
        return out
