"""XLA mesh collective backend — the TPU data plane.

Role-equivalent of the reference's NCCL ops
(reference: horovod/common/ops/nccl_operations.cc — ``NCCLAllreduce``
60-109, ``NCCLHierarchicalAllreduce`` 167-372), re-founded on XLA: the
negotiated (fused) Response is executed as a jit-compiled collective
over a ``jax.sharding.Mesh`` with one representative device per
process, so the bytes ride ICI/DCN and never touch the host NIC.

Why this is correct in multi-controller JAX: every process must issue
identical XLA computations in identical order. The coordinator's
broadcast ResponseList establishes exactly that total order (see
common/coordinator.py), so each process independently arriving here will
request the same compiled executable with the same shapes.

Compiled executables are cached per (op, shape-signature, dtype) — the
TPU-native realization of the reference's fusion-buffer reuse
(reference: common/fusion_buffer_manager.cc:21-45): instead of one
persistent scratch buffer, we keep one persistent *program* per bucket
signature, and XLA reuses its own buffers across calls.

Enabled only when a multi-process JAX world exists
(``jax.process_count() > 1``); single-process worlds take the in-jit
SPMD path (horovod_tpu/spmd) or the local backend instead.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from horovod_tpu.common import lockdep
from horovod_tpu.common import logging as hlog
from horovod_tpu.common import trace as htrace
from horovod_tpu.common.message import Response
from horovod_tpu.common.status import Status
from horovod_tpu.ops.backend import CollectiveBackend

_AXIS = "hvd_proc"
# Compiled executables XlaMeshBackend holds at most.
_CACHE_MAX = 256


def _shard_map(body, mesh, in_specs, out_specs):
    """Version-portable shard_map with the replication checker off
    (collectives guarantee their own output sharding; the static
    checker cannot see that). The version gate lives in the sanctioned
    compat shim."""
    from horovod_tpu.compat import jaxshim
    return jaxshim.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs)


def ragged_psum_wins(sizes, slice_numels, world_size: int) -> bool:
    """Skew guard for the fused variable-dim0 allgather: True when the
    masked-psum rendering moves fewer bytes than the padded all_gather.

    The padded all_gather's wire traffic scales with
    ``world_size x max(dim0)`` per entry, the reference's
    ``MPI_Allgatherv`` with the TRUE bytes
    (reference: mpi_operations.cc:95-173). A psum over a zero-scattered
    output buffer moves ~2x the true bytes (reduce-scatter +
    all-gather phases), so it wins once the skew exceeds roughly
    ``max(dim0) > 2 x mean(dim0)``. Inputs come from the broadcast
    Response (entry-major ``sizes``), so every rank decides alike.
    """
    if world_size <= 1:
        return False
    padded_elems = 0
    psum_elems = 0
    for ec, sn in enumerate(slice_numels):
        rows = sizes[ec * world_size:(ec + 1) * world_size]
        m = max(rows)
        padded_elems += world_size * m * sn
        # psum buffer: true rows + one max-block of overlap slack
        psum_elems += (sum(rows) + m) * sn
    if psum_elems > np.iinfo(np.int32).max:
        # The psum rendering scatters blocks at element offsets that
        # must index its assembled buffer; past int32 range a
        # 32-bit offset (jax canonicalizes int64 down without
        # jax_enable_x64) would silently wrap and corrupt the
        # output — the padded all_gather has no such offsets, so it
        # carries oversized buffers regardless of skew.
        return False
    return 2 * psum_elems < padded_elems


class XlaMeshBackend(CollectiveBackend):
    name = "xla_mesh"

    def __init__(self, controller, config=None):
        self._ctl = controller
        self._config = config
        self._lock = lockdep.lock("xla_ops.XlaMeshBackend._lock")
        self._mesh = None
        self._mesh2d = None   # (cross, local) factored mesh, see below
        self._my_device = None
        self._cache: Dict[Tuple, object] = {}
        self._available = None
        self._m_compiles = None  # set by attach_metrics
        self._m_cache_size = None

    def attach_metrics(self, registry) -> None:
        super().attach_metrics(registry)
        # Compilation is the mesh plane's dominant first-use cost; a
        # climbing compile count in steady state means shape churn is
        # defeating the executable cache.
        self._m_compiles = registry.counter(
            "hvd_xla_compiles_total",
            "collective executables built (shard_map jit)")
        self._m_cache_size = registry.gauge(
            "hvd_xla_compiled_cache_size",
            "distinct compiled collective executables held")

    def _rank_fn(self):
        return self._ctl.rank

    def _size_fn(self):
        return self._ctl.size

    def _probe_local(self) -> bool:
        """This rank's view of mesh availability (may be wrong on other
        ranks — never act on it alone)."""
        try:
            import jax
            if jax.process_count() <= 1:
                return False
            if jax.process_count() != self._size_fn():
                hlog.warning(
                    f"JAX world has {jax.process_count()} processes but "
                    f"horovod world has {self._size_fn()}; disabling the "
                    "XLA mesh backend.")
                return False
            if jax.process_index() != self._rank_fn():
                # Mesh slot r is interpreted as horovod rank r (broadcast
                # roots, allgather slots, alltoall blocks); if the
                # launcher numbered ranks differently from JAX process
                # indices, results would be silently permuted.
                hlog.warning(
                    f"horovod rank {self._rank_fn()} != jax process index "
                    f"{jax.process_index()}; disabling the XLA mesh "
                    "backend (collectives fall back to the socket path).")
                return False
            from horovod_tpu.compat import jaxshim
            # One representative device per process, ordered by the
            # horovod rank == jax process index contract established by
            # the launcher (run/launch.py exports both).
            by_proc: Dict[int, list] = {}
            for d in jax.devices():
                by_proc.setdefault(d.process_index, []).append(d)
            reps = [sorted(by_proc[p], key=lambda d: d.id)[0]
                    for p in sorted(by_proc)]
            self._mesh = jaxshim.make_raw_mesh(np.array(reps), (_AXIS,))
            self._my_device = reps[jax.process_index()]
            self._maybe_build_hierarchical_mesh(reps)
            return True
        except Exception as e:  # jax missing / not distributed
            hlog.warning(f"XLA mesh backend unavailable, collectives "
                         f"take the socket plane: {e!r}")
            return False

    def _maybe_build_hierarchical_mesh(self, reps) -> None:
        """HOROVOD_HIERARCHICAL_ALLREDUCE / _ALLGATHER: factor the flat
        proc mesh into (cross, local) axes so collectives decompose
        into an intra-host stage riding ICI and a cross-host stage
        riding DCN — the XLA rendering of NCCLHierarchicalAllreduce's
        reduce-scatter → cross allreduce → allgather (reference:
        nccl_operations.cc:167-372) and MPIHierarchicalAllgather's
        node-shared buffer + cross allgatherv (reference:
        mpi_operations.cc:179-329). Allreduce is order-free; the
        hierarchical allgather reshapes (cross, local) back into rank
        order, which the contiguous per-host rank layout guarantees.
        Other rank-ordered ops (alltoall, broadcast roots) stay on the
        flat mesh where slot r is unambiguously rank r."""
        from horovod_tpu.compat import jaxshim
        cfg = self._config
        topo = self._ctl.topology
        if cfg is None or topo is None or not (
                getattr(cfg, "hierarchical_allreduce", False)
                or getattr(cfg, "hierarchical_allgather", False)):
            return
        if not topo.is_homogeneous or topo.local_size <= 1:
            return
        # Requires the launcher's contiguous per-host rank layout
        # (rank == cross_rank * local_size + local_rank).
        if topo.rank != topo.cross_rank * topo.local_size + \
                topo.local_rank:
            hlog.warning("hierarchical collectives disabled (allreduce/"
                         "allgather): ranks are not grouped "
                         "contiguously per host")
            return
        grid = np.array(reps).reshape(topo.cross_size, topo.local_size)
        self._mesh2d = jaxshim.make_raw_mesh(grid, ("cross", "local"))

    def _ensure_mesh(self) -> bool:
        if self._available is not None:
            return self._available
        # The decision must be world-consistent: if any rank can't join
        # the mesh (jax init failed, rank permutation, device mismatch),
        # EVERY rank must take the socket path or the job deadlocks with
        # some ranks inside a psum and others in a TCP gather. All ranks
        # reach this point at the same position of the negotiated
        # response stream, so the agreement round is ordered identically
        # everywhere.
        local_ok = self._probe_local()
        self._available = self._ctl.agree(local_ok)
        if local_ok and not self._available:
            hlog.warning("XLA mesh backend disabled: another rank "
                         "cannot join the device mesh; all collectives "
                         "take the socket path.")
        return self._available

    def enabled(self, entries, response) -> bool:
        if self._size_fn() <= 1:
            return False
        # Only device tensors (jax arrays) take the mesh path; host numpy
        # tensors fall through to the socket backend, mirroring the
        # reference's CPU-tensors-use-MPI split
        # (reference: operations.cc:125-158 op registration order).
        if any(e.context != "jax" for e in entries):
            return False
        return self._ensure_mesh()

    # ------------------------------------------------------------------
    def _global_input(self, flat, mesh=None, axes=_AXIS):
        """Wrap this process's flat buffer as one shard of a global array
        over the proc axis (or the factored (cross, local) axes)."""
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.compat import jaxshim
        size = self._size_fn()
        local = jax.device_put(flat, self._my_device)
        return jax.make_array_from_single_device_arrays(
            (size * flat.shape[0],) + flat.shape[1:],
            jaxshim.named_sharding(mesh or self._mesh, P(axes)), [local])

    def _compiled(self, key, builder):
        with self._lock:
            fn = self._cache.get(key)
            if fn is None:
                fn = builder()
                # Every attribute a program bakes in is in its key
                # (shapes, scales, sizes, axes, _verdict_sig), so an
                # entry never goes stale; the bound only caps what
                # shape churn can hold. Oldest first.
                if len(self._cache) >= _CACHE_MAX:
                    del self._cache[next(iter(self._cache))]
                self._cache[key] = fn
                if self._m_compiles is not None:
                    self._m_compiles.inc()
                    self._m_cache_size.set(len(self._cache))
        return fn

    @staticmethod
    def _verdict_sig(response):
        """The negotiated attributes a compiled program bakes in beyond
        its shapes: the coordinator-stamped wire dtype and algorithm.
        Without them an autotune verdict flip (e.g. ALG_DEFAULT ->
        ALG_TWOLEVEL, or a wire-dtype move) would replay the stale
        program keyed only on (op, shape, dtype)."""
        if response is None:
            return ()
        return (response.wire_dtype, response.algorithm)

    def _run_shard_op(self, kind: str, flat, out_specs, body, extra=(),
                      mesh=None, axes=_AXIS, response=None):
        """jit(shard_map(body)) over the proc mesh, one shard per rank."""
        import jax
        from jax.sharding import PartitionSpec as P
        mesh = mesh or self._mesh
        key = (kind, flat.shape, str(flat.dtype), extra, axes,
               self._verdict_sig(response))

        def build():
            # Replication checker off (_shard_map): it can't statically
            # infer all_gather/psum results are replicated; semantics
            # are guaranteed by the collective itself.
            m = _shard_map(body, mesh=mesh,
                           in_specs=P(axes), out_specs=out_specs)
            return jax.jit(m)

        fn = self._compiled(key, build)
        garr = self._global_input(flat, mesh=mesh, axes=axes)
        out = fn(garr)
        return out

    @staticmethod
    def _observe(outs) -> Status:
        """Block until the issued collective's outputs are done."""
        try:
            import jax
            jax.block_until_ready(outs)
            return Status.OK()
        except Exception as ex:
            return Status.UnknownError(
                f"async collective completion failed: {ex!r}")

    def _complete(self, entries) -> Status:
        """Async completion (reference: FinalizeCUDAQueue,
        cuda_operations.cc:148-179): the jitted collective is already
        in flight; hand the output arrays to a finalizer thread that
        observes readiness and fires the callbacks, and return
        InProgress so the negotiation loop keeps cycling."""
        fin = self.finalizer
        if fin is None:
            return Status.OK()
        outs = [e.output for e in entries]
        cycle = htrace.current_cycle()   # of the batch's hvd.execute

        def finalize():
            st = self._observe(outs)
            with htrace.span("hvd.complete", cycle, len(entries)):
                for e in entries:
                    if e.callback:
                        try:
                            e.callback(st)
                        except Exception as ex:
                            # One adapter callback must not starve the
                            # rest of the batch of their completions.
                            hlog.error(f"completion callback for "
                                       f"{e.tensor_name} raised: {ex!r}")

        if not fin.submit(finalize):
            # Draining: observe readiness inline; the loop fires the
            # callbacks synchronously on a non-InProgress status.
            return self._observe(outs)
        return Status.InProgress()

    # -- allreduce -------------------------------------------------------
    def execute_allreduce(self, entries, response: Response) -> Status:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        arrays = [e.tensor for e in entries]
        sizes = [int(np.prod(np.asarray(a.shape))) if a.ndim else 1
                 for a in arrays]
        flat = (jnp.concatenate([jnp.ravel(a) for a in arrays])
                if len(arrays) > 1 else jnp.ravel(arrays[0]))
        pre, post = response.prescale_factor, response.postscale_factor
        # Factored (cross, local) psum when hierarchical allreduce is
        # on: XLA emits the intra-host stage on ICI and the cross-host
        # stage on DCN.
        if self._mesh2d is not None and getattr(
                self._config, "hierarchical_allreduce", False):
            mesh, axes = self._mesh2d, ("cross", "local")
        else:
            mesh, axes = self._mesh, _AXIS

        def body(x):
            if pre != 1.0:
                x = x * jnp.asarray(pre, x.dtype)
            y = jax.lax.psum(x, axes)
            if post != 1.0:
                y = y * jnp.asarray(post, y.dtype)
            return y

        out = self._run_shard_op("allreduce", flat, P(), body,
                                 extra=(pre, post), mesh=mesh, axes=axes,
                                 response=response)
        fused = out.addressable_data(0)
        offset = 0
        for e, a, n in zip(entries, arrays, sizes):
            e.output = jax.device_put(
                fused[offset:offset + n].reshape(a.shape))
            offset += n
        return self._complete(entries)

    # -- allgather (variable dim0 via pad + slice; fused multi-entry) ----
    def execute_allgather(self, entries, response: Response) -> Status:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        size = self._size_fn()
        sizes = response.tensor_sizes  # entry-major: [ec*size + rc]
        hier = (self._mesh2d is not None and getattr(
            self._config, "hierarchical_allgather", False))
        # Ragged-skew guard: under heavy dim-0 skew the padded
        # all_gather's N x max wire bytes dwarf the true payload; the
        # masked-psum rendering moves ~2x the TRUE bytes instead. The
        # decision is a pure function of the broadcast response, so
        # every rank picks the same rendering. Flat mesh only: under
        # hierarchical allgather the displaced cost is the two-level
        # gather's, which the byte model doesn't describe, and the
        # psum would cross DCN undecomposed.
        slice_numels = []
        for ec, e in enumerate(entries):
            sn = 1
            for d in e.tensor.shape[1:]:
                sn *= int(d)
            slice_numels.append(sn)
        if not hier and ragged_psum_wins(sizes, slice_numels, size):
            return self._execute_allgather_psum(entries, response,
                                                slice_numels)
        # Pad every entry to its own max dim-0, flatten, concatenate:
        # one all_gather moves the whole fused batch — the TPU
        # rendering of the reference's fused MPI_Allgatherv
        # (reference: mpi_operations.cc:95-173).
        max_dim0s, slices, flats = [], [], []
        for ec, e in enumerate(entries):
            x = e.tensor
            rows = sizes[ec * size:(ec + 1) * size]
            m = max(rows)
            pad = m - x.shape[0]
            if pad:
                x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
            max_dim0s.append(m)
            slices.append(tuple(x.shape[1:]))
            flats.append(jnp.ravel(x))
        flat = (jnp.concatenate(flats) if len(flats) > 1 else flats[0])

        if hier:
            # Two-level gather (reference: MPIHierarchicalAllgather,
            # mpi_operations.cc:179-329): gather the host's shards
            # locally (ICI), then exchange whole host blocks across
            # hosts (DCN). The (cross, local) result reshapes exactly
            # into rank order under the contiguous per-host layout.
            local_size = self._mesh2d.shape["local"]
            cross_size = self._mesh2d.shape["cross"]

            def body(t):
                g_local = jax.lax.all_gather(t, "local")
                g = jax.lax.all_gather(g_local, "cross")
                return g.reshape((cross_size * local_size,) + t.shape)

            out = self._run_shard_op(
                "allgather_hier", flat, P(), body,
                extra=(tuple(sizes),), mesh=self._mesh2d,
                axes=("cross", "local"), response=response)
        else:
            def body(t):
                return jax.lax.all_gather(t, _AXIS)

            out = self._run_shard_op("allgather", flat, P(), body,
                                     extra=(tuple(sizes),),
                                     response=response)
        # out: [size, sum(max_dim0_e*slice_e)] replicated; for each
        # entry slice each rank's real rows out of its padded block.
        g = out.addressable_data(0)
        ent_off = 0
        for ec, e in enumerate(entries):
            rows = sizes[ec * size:(ec + 1) * size]
            slice_shape = slices[ec]
            slice_numel = slice_numels[ec]
            block = max_dim0s[ec] * slice_numel
            parts = [
                g[r][ent_off:ent_off + rows[r] * slice_numel].reshape(
                    (rows[r],) + slice_shape)
                for r in range(size)]
            e.output = jax.device_put(
                jnp.concatenate(parts, axis=0) if size > 1
                else parts[0])
            ent_off += block
        return self._complete(entries)

    def _execute_allgather_psum(self, entries, response: Response,
                                slice_numels) -> Status:
        """Skewed (allgatherv-shaped) fused allgather: each rank
        zero-scatters its padded block at its TRUE row offset into a
        buffer laid out by real row counts, and one psum assembles the
        result — wire bytes track ~2x the true payload instead of the
        padded path's N x max (the guard in execute_allgather picks
        this rendering only when that is the cheaper side; reference
        behavior target: MPI_Allgatherv, mpi_operations.cc:95-173).

        Correctness of the overlap: rank r's padded block spans
        ``[off_r, off_r + max*sn)`` while rank r+1's rows begin at
        ``off_r + rows_r*sn`` — every position a rank does not own
        receives only its padding ZEROS, so the psum reconstructs each
        row exactly once. One trailing max-block of slack per entry
        keeps the last rank's padded block in bounds."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        size = self._size_fn()
        sizes = response.tensor_sizes
        max_dim0s, slice_shapes, flats = [], [], []
        rank_offsets = []   # [entry][rank] element offset of true rows
        total = 0
        for ec, e in enumerate(entries):
            x = e.tensor
            rows = sizes[ec * size:(ec + 1) * size]
            m = max(rows)
            sn = slice_numels[ec]
            pad = m - x.shape[0]
            if pad:
                x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
            max_dim0s.append(m)
            slice_shapes.append(tuple(x.shape[1:]))
            flats.append(jnp.ravel(x))
            offs, acc = [], 0
            for r in range(size):
                offs.append(total + acc * sn)
                acc += rows[r]
            rank_offsets.append(offs)
            total += (acc + m) * sn   # true rows + overlap slack
        flat = (jnp.concatenate(flats) if len(flats) > 1 else flats[0])
        # int64: ragged_psum_wins guarantees the total fits int32, but
        # the OFFSET arithmetic above (cumulative products) must never
        # wrap while computing it; with jax_enable_x64 the wide dtype
        # survives into the scatter as well.
        offs_const = np.asarray(rank_offsets, np.int64)  # [E, size]
        block_lens = [m * sn for m, sn in zip(max_dim0s, slice_numels)]

        def body(x):
            r = jax.lax.axis_index(_AXIS)
            buf = jnp.zeros((total,), x.dtype)
            in_off = 0
            for ec, blen in enumerate(block_lens):
                blk = jax.lax.dynamic_slice(x, (in_off,), (blen,))
                off = jnp.take(jnp.asarray(offs_const[ec]), r)
                buf = jax.lax.dynamic_update_slice(buf, blk, (off,))
                in_off += blen
            # psum promotes bool to int; each slot has exactly one
            # non-zero contributor, so casting back is exact.
            return jax.lax.psum(buf, _AXIS).astype(x.dtype)

        # slice_numels joins the key: the body's offsets/layout derive
        # from them, and same flat shape + sizes with different slice
        # widths would otherwise collide on a wrong executable.
        out = self._run_shard_op("allgather_psum", flat, P(), body,
                                 extra=(tuple(sizes),
                                        tuple(slice_numels)),
                                 response=response)
        g = out.addressable_data(0)
        for ec, e in enumerate(entries):
            rows = sizes[ec * size:(ec + 1) * size]
            sn = slice_numels[ec]
            ss = slice_shapes[ec]
            parts = [
                g[rank_offsets[ec][r]:
                  rank_offsets[ec][r] + rows[r] * sn].reshape(
                      (rows[r],) + ss)
                for r in range(size)]
            e.output = jax.device_put(
                jnp.concatenate(parts, axis=0) if size > 1
                else parts[0])
        return self._complete(entries)

    # -- broadcast (ncclBcast role, two renderings) ----------------------
    def execute_broadcast(self, entries, response: Response) -> Status:
        """Fills the ncclBcast role (reference:
        nccl_operations.cc:334-351). Two renderings, selected by
        HOROVOD_XLA_BCAST (no native one-to-all collective exists at
        the jax level — ppermute forbids multicast sources):

        * ``psum`` (default): mask to the root's contribution and
          psum. One fused, pipelined collective; ~2x payload per link
          (allreduce bandwidth) but single-round. Measured fastest on
          8-way worlds (benchmarks/collective_bench.py
          broadcast_rendering).
        * ``tree``: binary-tree ppermute chain; every device receives
          the payload exactly once (N-1 transfers over the fabric vs
          the psum's ~2N) at ceil(log2 N) sequential rounds of
          latency. Wins on small worlds / congested fabrics.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        (entry,) = entries
        x = entry.tensor
        root = entry.root_rank
        size = self._size_fn()
        flat = jnp.ravel(x)  # 0-d scalars are legal for broadcast
        rendering = getattr(self._config, "xla_broadcast", "psum") \
            if self._config is not None else "psum"

        if rendering == "tree":
            def body(t):
                idx = jax.lax.axis_index(_AXIS)
                v = (idx - root) % size  # virtual index: root is 0
                cur = t
                k = 1
                while k < size:
                    perm = [((u + root) % size, (u + k + root) % size)
                            for u in range(k) if u + k < size]
                    received = jax.lax.ppermute(cur, _AXIS, perm=perm)
                    cur = jnp.where((v >= k) & (v < 2 * k), received,
                                    cur)
                    k *= 2
                return cur

            out = self._run_shard_op("broadcast", flat, P(_AXIS), body,
                                     extra=(root, "tree"),
                                     response=response)
        else:
            def body(t):
                idx = jax.lax.axis_index(_AXIS)
                contrib = jnp.where(idx == root, t, jnp.zeros_like(t))
                return jax.lax.psum(contrib, _AXIS)

            out = self._run_shard_op("broadcast", flat, P(), body,
                                     extra=(root, "psum"),
                                     response=response)
        entry.output = jax.device_put(
            out.addressable_data(0).reshape(x.shape))
        return self._complete(entries)

    # -- alltoall --------------------------------------------------------
    def execute_alltoall(self, entries, response: Response) -> Status:
        import jax
        from jax.sharding import PartitionSpec as P

        (entry,) = entries
        x = entry.tensor

        def body(t):
            # tiled all_to_all: split dim 0 into `size` blocks, exchange,
            # re-concatenate along dim 0 — block d of the output came
            # from rank d.
            return jax.lax.all_to_all(t, _AXIS, split_axis=0,
                                      concat_axis=0, tiled=True)

        out = self._run_shard_op("alltoall", x, P(_AXIS), body,
                                 response=response)
        entry.output = jax.device_put(out.addressable_data(0))
        return self._complete(entries)

    # -- reducescatter ---------------------------------------------------
    def execute_reducescatter(self, entries, response: Response) -> Status:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.compat import jaxshim

        (entry,) = entries
        x = entry.tensor
        size = self._size_fn()
        pre, post = response.prescale_factor, response.postscale_factor

        def body(t):
            if pre != 1.0:
                t = t * jnp.asarray(pre, t.dtype)
            y = jaxshim.psum_scatter(
                t.reshape((size, t.shape[0] // size) + t.shape[1:]),
                _AXIS, scatter_dimension=0, tiled=False)
            if post != 1.0:
                y = y * jnp.asarray(post, y.dtype)
            return y

        out = self._run_shard_op("reducescatter", x, P(_AXIS), body,
                                 extra=(pre, post), response=response)
        entry.output = jax.device_put(out.addressable_data(0))
        return self._complete(entries)

    def execute_barrier(self, entries, response: Response) -> Status:
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        import jax

        def body(t):
            return jax.lax.psum(t, _AXIS)

        self._run_shard_op("barrier", jnp.zeros((1,), jnp.float32),
                           P(), body).block_until_ready()
        return Status.OK()
