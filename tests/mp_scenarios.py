"""Multi-process test scenarios, run as subprocesses by
test_multiprocess.py — the TPU build's analog of the reference running
its pytest suite under ``mpirun -np 2`` (reference: .travis.yml:109-122).

Each scenario function runs on every rank with hvd initialized; it must
assert its own correctness and return. Invoked as:

    python -m tests.mp_scenarios <scenario> <rank> <size> <port>
"""

import os
import sys

import numpy as np


def scenario_allreduce(hvd, rank, size):
    x = np.full((4, 3), float(rank + 1), np.float32)
    out = hvd.allreduce(x, average=False, name="ar")
    expected = np.full((4, 3), sum(range(1, size + 1)), np.float32)
    np.testing.assert_allclose(out, expected)
    # average
    out = hvd.allreduce(x, average=True, name="ar_avg")
    np.testing.assert_allclose(
        out, expected / size)


def scenario_allreduce_fused(hvd, rank, size):
    """Many small async allreduces in one cycle → fused execution
    (reference analog: test_horovod_allreduce_cpu_fused,
    test_tensorflow.py:107)."""
    handles = [hvd.allreduce_async(
        np.full(10, float(rank + 1) * (i + 1), np.float64),
        average=False, name=f"f/{i}") for i in range(30)]
    ssum = sum(range(1, size + 1))
    for i, h in enumerate(handles):
        np.testing.assert_allclose(
            hvd.synchronize(h), np.full(10, ssum * (i + 1), np.float64))


def scenario_allreduce_multi_dtype(hvd, rank, size):
    for dt in (np.int32, np.int64, np.float16, np.float32, np.float64):
        x = (np.arange(6) + rank).astype(dt)
        out = hvd.allreduce(x, average=False, name=f"dt/{np.dtype(dt)}")
        expected = (size * np.arange(6) + sum(range(size))).astype(dt)
        np.testing.assert_allclose(np.asarray(out, np.float64),
                                   expected.astype(np.float64))


def scenario_allgather(hvd, rank, size):
    # variable dim-0 per rank (reference: test_tensorflow.py:454-557)
    x = np.full((rank + 1, 2), float(rank), np.float32)
    out = hvd.allgather(x, name="ag")
    assert out.shape == (sum(r + 1 for r in range(size)), 2)
    offset = 0
    for r in range(size):
        np.testing.assert_allclose(out[offset:offset + r + 1],
                                   np.full((r + 1, 2), float(r)))
        offset += r + 1


def scenario_broadcast(hvd, rank, size):
    for root in range(size):
        x = np.full((3, 3), float(rank * 10), np.float64)
        out = hvd.broadcast(x, root_rank=root, name=f"bc/{root}")
        np.testing.assert_allclose(out, np.full((3, 3), float(root * 10)))


def scenario_alltoall(hvd, rank, size):
    per = 2
    x = np.arange(size * per, dtype=np.float32) + 100 * rank
    out = hvd.alltoall(x, name="a2a")
    expected = np.concatenate(
        [np.arange(rank * per, (rank + 1) * per) + 100 * src
         for src in range(size)]).astype(np.float32)
    np.testing.assert_allclose(out, expected)


def scenario_reducescatter(hvd, rank, size):
    x = np.arange(size * 3, dtype=np.float32) * (rank + 1)
    out = hvd.reducescatter(x, name="rs")
    ssum = sum(range(1, size + 1))
    expected = (np.arange(size * 3, dtype=np.float32)
                * ssum)[rank * 3:(rank + 1) * 3]
    np.testing.assert_allclose(out, expected)


def scenario_ring_allreduce(hvd, rank, size):
    """Payloads over the (harness-lowered) threshold ride the ring data
    plane; small ones keep the star; reducescatter reuses the same ring.
    (Reference analog: MPI_Allreduce's internal ring algorithms,
    mpi_operations.cc:25-84.)"""
    from horovod_tpu.common import basics as _b
    ssum = sum(range(1, size + 1))

    n = 100_000
    x = np.arange(n, dtype=np.float64) + rank
    out = hvd.allreduce(x, average=False, name="ring.big")
    np.testing.assert_allclose(
        out, size * np.arange(n, dtype=np.float64) + sum(range(size)))

    rt = _b.runtime()
    sock = [b for b in rt.op_manager._backends if b.name == "socket"][0]
    assert sock._ring is not None, "ring was not established"

    # below threshold -> star path, after the ring already exists
    y = np.full(8, float(rank + 1), np.float32)
    np.testing.assert_allclose(
        hvd.allreduce(y, average=False, name="ring.small"), ssum)

    # non-in-place contract: the caller's array must survive the ring
    z = np.full(50_000, float(rank + 1), np.float32)
    out = hvd.allreduce(z, average=True, name="ring.big2")
    np.testing.assert_allclose(out, ssum / size)
    np.testing.assert_allclose(z, float(rank + 1))

    # fused batch over the threshold -> one ring op for the whole pack
    handles = [hvd.allreduce_async(
        np.full(20_000, float(rank + 1) * (i + 1), np.float64),
        average=False, name=f"ring.f/{i}") for i in range(4)]
    for i, h in enumerate(handles):
        np.testing.assert_allclose(
            hvd.synchronize(h), ssum * (i + 1))

    # reducescatter on the same ring (phase-1-only schedule)
    per = 4096
    rs = np.arange(size * per, dtype=np.float64) * (rank + 1)
    out = hvd.reducescatter(rs, name="ring.rs")
    expected = (np.arange(size * per, dtype=np.float64)
                * ssum)[rank * per:(rank + 1) * per]
    np.testing.assert_allclose(out, expected)


def scenario_ring_fallback(hvd, rank, size):
    """Ring establishment failing on ONE rank must degrade the whole
    world to the star path by agreement (ops/ring.py establish():
    port -1 advertisement + agree()) — no divergence, results correct."""
    from horovod_tpu.common import basics as _b
    from horovod_tpu.common import network as _net

    orig_listen = _net.listen
    if rank == 1:
        def _fail(*a, **k):
            raise OSError("forced listen failure (test)")
        _net.listen = _fail

    x = np.full(100_000, float(rank + 1), np.float64)
    out = hvd.allreduce(x, average=False, name="rf.big")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))

    rt = _b.runtime()
    sock = [b for b in rt.op_manager._backends if b.name == "socket"][0]
    assert sock._ring_tried, "ring establishment was never attempted"
    assert sock._ring is None, "ring must not exist after a failed vote"

    _net.listen = orig_listen
    # the world stays on the star path (establishment is tried once)
    out = hvd.allreduce(x, average=False, name="rf.big2")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))


def scenario_shm_collectives(hvd, rank, size):
    """All five collectives + fused batch + segment growth on the
    shared-memory backend (same-host world selects it automatically)."""
    from horovod_tpu.common import basics as _b
    rt = _b.runtime()
    shm = [b for b in rt.op_manager._backends if b.name == "shm"][0]
    ssum = sum(range(1, size + 1))

    # allreduce (small -> establishes the first segment)
    x = np.full((4, 3), float(rank + 1), np.float32)
    np.testing.assert_allclose(
        hvd.allreduce(x, average=False, name="shm.ar"),
        np.full((4, 3), ssum, np.float32))
    assert shm._map is not None, "shm segment not established"
    gen0 = shm._gen

    # large allreduce -> segment must grow (re-establishment)
    big = np.arange(300_000, dtype=np.float64) + rank
    np.testing.assert_allclose(
        hvd.allreduce(big, average=False, name="shm.big"),
        size * np.arange(300_000, dtype=np.float64) + sum(range(size)))
    assert shm._gen > gen0, "segment did not grow for the larger payload"

    # input must never be mutated (slots are written, results copied out)
    np.testing.assert_allclose(big, np.arange(300_000) + rank)

    # fused batch in one cycle
    handles = [hvd.allreduce_async(
        np.full(1000, float(rank + 1) * (i + 1), np.float64),
        average=False, name=f"shm.f/{i}") for i in range(8)]
    for i, h in enumerate(handles):
        np.testing.assert_allclose(
            hvd.synchronize(h), ssum * (i + 1))

    # variable-dim0 allgather
    g = hvd.allgather(
        np.full((rank + 1, 2), float(rank), np.float32), name="shm.ag")
    assert g.shape == (sum(r + 1 for r in range(size)), 2)
    offset = 0
    for r in range(size):
        np.testing.assert_allclose(g[offset:offset + r + 1], float(r))
        offset += r + 1

    # broadcast from every root (incl. non-coordinator roots)
    for root in range(size):
        out = hvd.broadcast(np.full(5, float(rank * 10), np.float64),
                            root_rank=root, name=f"shm.bc/{root}")
        np.testing.assert_allclose(out, float(root * 10))

    # alltoall
    per = 2
    a = np.arange(size * per, dtype=np.float32) + 100 * rank
    out = hvd.alltoall(a, name="shm.a2a")
    expected = np.concatenate(
        [np.arange(rank * per, (rank + 1) * per) + 100 * src
         for src in range(size)]).astype(np.float32)
    np.testing.assert_allclose(out, expected)

    # reducescatter
    rs = np.arange(size * 3, dtype=np.float32) * (rank + 1)
    out = hvd.reducescatter(rs, name="shm.rs")
    np.testing.assert_allclose(
        out, (np.arange(size * 3, dtype=np.float32)
              * ssum)[rank * 3:(rank + 1) * 3])

    hvd.barrier(name="shm.bar")


def scenario_edge_shapes(hvd, rank, size):
    """Zero-size and 0-d tensors through the collectives: negotiated
    like anything else, correct shapes out, no wedged protocol. Run
    under both the shm and socket planes by the harness."""
    z = hvd.allreduce(np.empty(0, np.float32), average=False,
                      name="e.zero")
    assert np.asarray(z).shape == (0,)

    out = hvd.allreduce(np.asarray(3.0 * (rank + 1), np.float64),
                        average=False, name="e.scalar")
    assert np.asarray(out).shape == ()
    assert float(out) == 3.0 * sum(range(1, size + 1))

    # every rank empty
    g = hvd.allgather(np.empty((0, 4), np.float32), name="e.ag0")
    assert np.asarray(g).shape == (0, 4)

    # SOME ranks empty (rank 0 contributes nothing)
    g = hvd.allgather(np.full((rank, 2), float(rank), np.float32),
                      name="e.ag_some")
    assert np.asarray(g).shape == (sum(range(size)), 2)
    offset = 0
    for r in range(size):
        np.testing.assert_allclose(np.asarray(g)[offset:offset + r],
                                   float(r))
        offset += r

    b = hvd.broadcast(np.empty(0, np.float64), root_rank=size - 1,
                      name="e.bc0")
    assert np.asarray(b).shape == (0,)

    # the world still works afterwards
    out = hvd.allreduce(np.full(5, float(rank + 1), np.float32),
                        average=False, name="e.after")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))


def scenario_mixed_op_storm(hvd, rank, size):
    """30 mixed collectives submitted asynchronously in a DIFFERENT
    random order on every rank: the coordinator must serialize them
    into one agreed schedule and complete every handle with the right
    value — the core negotiation promise (reference spirit:
    test_torch.py's out-of-order and partial-participation legs)."""
    rng = np.random.RandomState(1000 + rank)  # per-rank order!
    ssum = sum(range(1, size + 1))

    jobs = []
    for i in range(10):
        jobs.append(("ar", i))
        jobs.append(("bc", i))
        jobs.append(("ag", i))
    order = rng.permutation(len(jobs))

    handles = {}
    for idx in order:
        kind, i = jobs[idx]
        if kind == "ar":
            handles[("ar", i)] = hvd.allreduce_async(
                np.full(64 + i, float(rank + 1) * (i + 1), np.float64),
                average=False, name=f"storm.ar{i}")
        elif kind == "bc":
            handles[("bc", i)] = hvd.broadcast_async(
                np.full(8, float(rank * 100 + i), np.float32),
                root_rank=i % size, name=f"storm.bc{i}")
        else:
            handles[("ag", i)] = hvd.allgather_async(
                np.full((rank + 1, 2), float(rank * 10 + i),
                        np.float32), name=f"storm.ag{i}")

    for i in range(10):
        np.testing.assert_allclose(
            hvd.synchronize(handles[("ar", i)]), ssum * (i + 1))
        np.testing.assert_allclose(
            hvd.synchronize(handles[("bc", i)]),
            float((i % size) * 100 + i))
        g = hvd.synchronize(handles[("ag", i)])
        assert np.asarray(g).shape == (sum(r + 1 for r in range(size)),
                                       2)
        offset = 0
        for r in range(size):
            np.testing.assert_allclose(
                np.asarray(g)[offset:offset + r + 1],
                float(r * 10 + i))
            offset += r + 1


def scenario_grouped_allreduce(hvd, rank, size):
    """grouped_allreduce: one call, many tensors, derived names agreed
    across ranks; mixed dtypes split into separate fusion batches but
    every member completes with exact values. The blocking form drains
    every member even when one errors (all-or-nothing surfacing)."""
    from horovod_tpu.common.status import HorovodInternalError

    ssum = sum(range(1, size + 1))
    tensors = [np.full(16 + i, float(rank + 1) * (i + 1), np.float64)
               for i in range(6)]
    tensors.append(np.full(4, rank + 1, np.int64))  # dtype break
    outs = hvd.grouped_allreduce(tensors, average=False, name="grp")
    for i in range(6):
        np.testing.assert_allclose(outs[i],
                                   np.full(16 + i, ssum * (i + 1.0)))
    np.testing.assert_allclose(np.asarray(outs[6], np.float64),
                               float(ssum))

    # average semantics apply per member
    avg = hvd.grouped_allreduce(
        [np.full(3, float(rank + 1) * 2, np.float32)], name="grp.avg")
    np.testing.assert_allclose(avg[0], 2.0 * ssum / size)

    # all-or-nothing: one member mismatched in shape across ranks ->
    # the group call raises, the good members still completed
    bad = [np.ones(5, np.float32),
           np.ones(4 + rank % 2, np.float32)]  # member 1 mismatches
    try:
        hvd.grouped_allreduce(bad, average=False, name="grp.bad")
    except HorovodInternalError as e:
        assert "shape" in str(e).lower()
    else:
        if size > 1:
            raise AssertionError("expected group member error")
    # the world remains usable
    ok = hvd.grouped_allreduce([np.ones(2, np.float32)],
                               average=False, name="grp.after")
    np.testing.assert_allclose(ok[0], float(size))

    # pre-validation: an unscalable member (int under Average) fails
    # the WHOLE call before anything is enqueued — no half-submitted
    # group for peers to block on
    try:
        hvd.grouped_allreduce([np.ones(2, np.float32),
                               np.ones(2, np.int32)], name="grp.val")
    except ValueError:
        pass
    else:
        raise AssertionError("expected ValueError for int average")
    ok = hvd.grouped_allreduce([np.ones(2, np.float32)],
                               average=False, name="grp.after2")
    np.testing.assert_allclose(ok[0], float(size))

    # pre-validation also covers unsupported DTYPES: a complex member
    # must fail the whole call before member 0 is enqueued (otherwise
    # member 0 would be left in flight and peers would hang on it)
    try:
        hvd.grouped_allreduce([np.ones(2, np.float32),
                               np.ones(2, np.complex64)],
                              average=False, name="grp.cplx")
    except ValueError:
        pass
    else:
        raise AssertionError("expected ValueError for complex dtype")
    ok = hvd.grouped_allreduce([np.ones(2, np.float32)],
                               average=False, name="grp.after3")
    np.testing.assert_allclose(ok[0], float(size))


def _record_batches(hvd):
    """Wrap the runtime's op dispatch to record every executed batch as
    (response_type_name, [tensor_names]) — lets scenarios assert HOW
    work was batched, not just that values are right."""
    from horovod_tpu.common import basics as _b
    rt = _b.runtime()
    seen = []
    orig = rt.op_manager.execute

    def wrapped(entries, response):
        seen.append((response.response_type.name,
                     list(response.tensor_names)))
        return orig(entries, response)

    rt.op_manager.execute = wrapped
    return seen


def scenario_fused_allgather(hvd, rank, size):
    """ALLGATHER responses fuse under the threshold like allreduce
    (reference: operations.cc:1172-1234): several small allgathers
    submitted together execute as multi-entry batches on every
    backend, with entry-major displacement unpack and variable dim-0
    per rank preserved per entry."""
    seen = _record_batches(hvd)

    handles, specs = [], []
    for i in range(6):
        # distinct slice shapes AND variable dim-0 per rank
        rows = rank + 1 + (i % 2)
        x = np.full((rows, i + 1), float(rank * 10 + i), np.float32)
        specs.append((rows, i + 1))
        handles.append(hvd.allgather_async(x, name=f"fag.{i}"))
    for i, h in enumerate(handles):
        out = hvd.synchronize(h)
        total_rows = sum(r + 1 + (i % 2) for r in range(size))
        assert out.shape == (total_rows, i + 1), (i, out.shape)
        off = 0
        for r in range(size):
            rr = r + 1 + (i % 2)
            np.testing.assert_allclose(
                out[off:off + rr], np.full((rr, i + 1),
                                           float(r * 10 + i)))
            off += rr

    ag_batches = [names for kind, names in seen if kind == "ALLGATHER"]
    assert any(len(b) >= 2 for b in ag_batches), \
        f"no fused allgather batch executed: {ag_batches}"

    # an int64 allgather must NOT fuse into a float32 batch
    seen.clear()
    h1 = hvd.allgather_async(np.full((2, 2), rank, np.float32),
                             name="fag.f32")
    h2 = hvd.allgather_async(np.full((2, 2), rank, np.int64),
                             name="fag.i64")
    hvd.synchronize(h1), hvd.synchronize(h2)
    for kind, names in seen:
        if kind == "ALLGATHER" and len(names) > 1:
            raise AssertionError(f"mixed-dtype allgather fused: {names}")

    # empty entries INSIDE a fused batch: one entry empty on every
    # rank, one empty on rank 0 only, one normal — displacement math
    # must keep zero-length components straight
    he = [hvd.allgather_async(np.empty((0, 3), np.float32),
                              name="fag.e.all"),
          hvd.allgather_async(np.full((rank, 3), float(rank),
                                      np.float32), name="fag.e.some"),
          hvd.allgather_async(np.full((2, 3), float(rank + 10),
                                      np.float32), name="fag.e.full")]
    out = hvd.synchronize(he[0])
    assert out.shape == (0, 3), out.shape
    out = hvd.synchronize(he[1])
    assert out.shape == (sum(range(size)), 3)
    off = 0
    for r in range(size):
        np.testing.assert_allclose(out[off:off + r], float(r))
        off += r
    out = hvd.synchronize(he[2])
    assert out.shape == (2 * size, 3)
    for r in range(size):
        np.testing.assert_allclose(out[2 * r:2 * r + 2], float(r + 10))


def scenario_sparse_allgather_fusion(hvd, rank, size):
    """The sparse-gradient traffic shape (TF IndexedSlices -> one
    values + one indices allgather per embedding tensor, the word2vec
    path): with allgather fusion, a step's 6 tensor pairs execute as
    ~2 fused batches (f32 values together, i64 indices together)
    instead of 12 negotiated singles (reference bar:
    operations.cc:1172-1234)."""
    seen = _record_batches(hvd)
    n_tensors = 6
    handles = []
    for t in range(n_tensors):
        rows = rank + 1 + t % 3
        handles.append((t, "v", hvd.allgather_async(
            np.full((rows, 8), float(rank * 10 + t), np.float32),
            name=f"sp.{t}.values")))
        handles.append((t, "i", hvd.allgather_async(
            np.arange(rows, dtype=np.int64) + rank * 100,
            name=f"sp.{t}.indices")))
    for t, kind, h in handles:
        out = np.asarray(hvd.synchronize(h))
        rows = [r + 1 + t % 3 for r in range(size)]
        assert out.shape[0] == sum(rows), (t, kind, out.shape)
        off = 0
        for r in range(size):
            if kind == "v":
                np.testing.assert_allclose(out[off:off + rows[r]],
                                           float(r * 10 + t))
            else:
                np.testing.assert_array_equal(
                    out[off:off + rows[r]],
                    np.arange(rows[r], dtype=np.int64) + r * 100)
            off += rows[r]
    batches = [names for k, names in seen if k == "ALLGATHER"]
    total = sum(len(b) for b in batches)
    assert total == 2 * n_tensors, (total, batches)
    # the whole step must collapse into a few fused batches, not one
    # negotiation+dispatch per tensor (cycle straddles may split once)
    assert len(batches) <= 6, [sorted(b) for b in batches]
    assert any(len(b) >= 3 for b in batches), batches


def scenario_grouped_atomic(hvd, rank, size):
    """Grouped allreduce atomicity is a guarantee, not best-effort:
    all members land in ONE fused response even with the default
    1 ms cycle ticking concurrently and another thread spamming its
    own singles (Runtime.enqueue_group holds the table lock across
    the whole insert)."""
    import threading

    seen = _record_batches(hvd)

    def spam():
        # Fixed count on every rank: a collective only some ranks
        # submit would deadlock the world (blocking allreduce paces
        # all ranks through the same 50 names).
        for i in range(50):
            hvd.allreduce(np.full(8, float(rank + 1), np.float32),
                          average=False, name=f"spam.{i}")

    spammer = threading.Thread(target=spam)
    spammer.start()
    try:
        for round_ in range(5):
            group = [np.full(16, float(rank + 1) * (i + 1), np.float32)
                     for i in range(8)]
            outs = hvd.grouped_allreduce(group, average=False,
                                         name=f"atom.{round_}")
            ssum = sum(range(1, size + 1))
            for i, o in enumerate(outs):
                np.testing.assert_allclose(o, ssum * (i + 1.0))
            want = {f"atom.{round_}.{i}" for i in range(8)}
            batches = [set(names) for kind, names in seen
                       if kind == "ALLREDUCE"]
            containing = [b for b in batches if b & want]
            assert len(containing) == 1 and want <= containing[0], \
                f"group {round_} split across batches: " \
                f"{[sorted(b & want) for b in containing]}"
    finally:
        spammer.join()
    # spam thread's own collectives must drain before shutdown
    hvd.barrier(name="atom.done")


def scenario_coordinator_fuzz(hvd, rank, size):
    """Randomized negotiation fuzz — the framework's race-detection
    analog (SURVEY §5: the coordinator protocol is what turns racy
    per-rank op ordering into a total order). A seeded job list of a
    few hundred mixed collectives (all 5 data ops × 4 dtypes × varied
    shapes, interleaved barriers) is submitted asynchronously in a
    DIFFERENT random order on every rank, in waves with partial drains
    so negotiation, fusion, and execution overlap; every handle's value
    is checked exactly."""
    jobs_rng = np.random.RandomState(4242)        # SAME on all ranks
    order_rng = np.random.RandomState(977 + rank)  # per-rank order
    ssum = sum(range(1, size + 1))
    dtypes = [np.float32, np.float64, np.int32, np.int64]

    jobs = []
    for i in range(240):
        kind = ["ar", "bc", "ag", "rs", "a2a"][jobs_rng.randint(5)]
        dt = dtypes[jobs_rng.randint(len(dtypes))]
        n = int(jobs_rng.randint(1, 90))
        root = int(jobs_rng.randint(size))
        jobs.append((i, kind, dt, n, root))

    def submit(job):
        i, kind, dt, n, root = job
        tag = f"fz.{i}"
        if kind == "ar":
            return hvd.allreduce_async(
                np.full(n, dt(rank + 1) * (i % 7 + 1), dt),
                average=False, name=tag)
        if kind == "bc":
            return hvd.broadcast_async(
                np.full(n, dt(rank * 100 + i), dt), root_rank=root,
                name=tag)
        if kind == "ag":
            return hvd.allgather_async(
                np.full((rank + 1, n), dt(rank * 10 + i), dt), name=tag)
        if kind == "rs":
            return hvd.reducescatter_async(
                (np.arange(size * n) + rank).astype(dt), name=tag)
        return hvd.alltoall_async(
            np.full((size * 2, n), dt(rank + i), dt), name=tag)

    def check(job, out):
        i, kind, dt, n, root = job
        out = np.asarray(out)
        if kind == "ar":
            np.testing.assert_allclose(
                out.astype(np.float64),
                np.full(n, float(ssum * (i % 7 + 1))))
        elif kind == "bc":
            np.testing.assert_allclose(
                out.astype(np.float64), float(root * 100 + i))
        elif kind == "ag":
            assert out.shape == (sum(r + 1 for r in range(size)), n)
            off = 0
            for r in range(size):
                np.testing.assert_allclose(
                    out[off:off + r + 1].astype(np.float64),
                    float(r * 10 + i))
                off += r + 1
        elif kind == "rs":
            base = size * np.arange(size * n) + sum(range(size))
            np.testing.assert_allclose(
                out.astype(np.float64),
                base[rank * n:(rank + 1) * n].astype(np.float64))
        else:
            assert out.shape == (size * 2, n)
            for r in range(size):
                np.testing.assert_allclose(
                    out[r * 2:(r + 1) * 2].astype(np.float64),
                    float(r + i))

    # waves with partial drains: in-flight ops from wave k overlap
    # wave k+1's negotiation
    pending = []
    for start in range(0, len(jobs), 60):
        wave = [jobs[j] for j in
                start + order_rng.permutation(
                    min(60, len(jobs) - start))]
        pending.extend((job, submit(job)) for job in wave)
        # Barrier decisions come from the SHARED rng: a collective only
        # some ranks submit would deadlock the world (which is exactly
        # what the stall inspector exists to report, but not what this
        # scenario tests).
        if jobs_rng.rand() < 0.5:
            hvd.barrier(name=f"fz.bar.{start}")
        # a grouped wave (shared decision + shared member count) rides
        # the same storm: atomic submission must hold under overlap
        if jobs_rng.rand() < 0.5:
            k = int(jobs_rng.randint(2, 7))
            gouts = hvd.grouped_allreduce(
                [np.full(12, float(rank + 1) * (m + 1), np.float32)
                 for m in range(k)],
                average=False, name=f"fz.grp.{start}")
            for m, o in enumerate(gouts):
                np.testing.assert_allclose(o, ssum * (m + 1.0))
        drain, pending = pending[:len(pending) // 2], \
            pending[len(pending) // 2:]
        for job, h in drain:
            check(job, hvd.synchronize(h))
    for job, h in pending:
        check(job, hvd.synchronize(h))


def _cache_runtime_stats(hvd):
    from horovod_tpu.common import basics as _b
    return _b.runtime().negotiation_cache_stats()


def _cache_fingerprint_crc(hvd) -> int:
    """CRC of the response cache's world-coherent state (slot map, LRU
    order, epoch) — allgathered across ranks to prove the caches
    marched in lockstep (Python hash() is process-seeded, crc32 is
    not)."""
    import zlib
    from horovod_tpu.common import basics as _b
    cache = _b.runtime()._cache
    return zlib.crc32(repr(cache.state_fingerprint()).encode())


def _assert_cache_coherent(hvd, rank, size, tag):
    """Every rank's cache fingerprint must be identical right now."""
    fp = _cache_fingerprint_crc(hvd)
    got = np.asarray(hvd.allgather(
        np.asarray([[fp]], np.int64), name=f"{tag}.fp"))
    assert (got == fp).all(), \
        f"rank {rank}: cache state diverged across ranks: {got.ravel()}"


def scenario_response_cache_steady(hvd, rank, size):
    """The steady-state negotiation fast path, end to end: a training-
    shaped loop resubmitting the same tensor set must (a) return exact
    values every step, (b) negotiate via the bitmask path (hit rate
    ~100%, fully cached cycles observed), (c) keep the cache state
    bit-identical across every rank, (d) invalidate coherently on
    shape and dtype changes and renegotiate exactly, and (e) survive
    skewed submission (a rank holding back a cached tensor: the
    others' hits stay queued un-granted until the straggler arrives)."""
    import time
    from horovod_tpu.common import basics as _b

    ssum = sum(range(1, size + 1))
    names = [f"rc.{i}" for i in range(8)]
    xs = [np.full(64 + i, float(rank + 1) * (i + 1), np.float64)
          for i in range(8)]

    def step(check=True):
        hs = [hvd.allreduce_async(x, average=False, name=nm)
              for x, nm in zip(xs, names)]
        for i, h in enumerate(hs):
            out = hvd.synchronize(h)
            if check:
                np.testing.assert_allclose(out, ssum * (i + 1.0))

    for _ in range(3):
        step()
    hvd.barrier(name="rc.bar")
    s0 = _cache_runtime_stats(hvd)
    assert s0["enabled"], "cache must be on by default"
    for _ in range(30):
        step()
    s1 = _cache_runtime_stats(hvd)
    d_hits = s1["hits"] - s0["hits"]
    d_misses = s1["misses"] - s0["misses"]
    rate = d_hits / max(1, d_hits + d_misses)
    assert rate >= 0.99, (rank, d_hits, d_misses, rate)
    assert s1["cached_cycles"] > s0["cached_cycles"], (rank, s0, s1)
    if os.environ.get("HOROVOD_TPU_SHM") == "0" \
            and os.environ.get("HOROVOD_CACHE_SPECULATIVE", "1") != "0":
        # Socket star data plane: the steady allreduce set must ride
        # the fused speculative round (shm/ring-bound batches keep
        # their own plane and legitimately never speculate).
        assert s1["spec_cycles"] > s0["spec_cycles"], (rank, s0, s1)
    _assert_cache_coherent(hvd, rank, size, "rc.a")

    # (d) SHAPE change: same names, new shapes -> slot invalidated on
    # every rank, renegotiated exactly, then hits resume
    xs = [np.full((3, 32 + i), float(rank + 1) * (i + 1), np.float64)
          for i in range(8)]
    step()
    _assert_cache_coherent(hvd, rank, size, "rc.b")
    s2 = _cache_runtime_stats(hvd)
    step()
    s3 = _cache_runtime_stats(hvd)
    assert s3["hits"] - s2["hits"] >= 8, (rank, s2, s3)  # hits resumed

    # DTYPE change on one tensor: only that slot invalidates
    xs[0] = np.full((3, 32), float(rank + 1), np.float32)
    step()
    _assert_cache_coherent(hvd, rank, size, "rc.c")
    step()

    # (e) skewed submission: every rank submits the cached rc.0 but
    # rank size-1 holds back for a while -- the others' hit bits stay
    # queued (requeued each cycle, never granted) until it arrives
    if rank == size - 1:
        time.sleep(0.4)
    out = hvd.allreduce(xs[0], average=False, name=names[0])
    np.testing.assert_allclose(np.asarray(out, np.float64), ssum * 1.0)
    _assert_cache_coherent(hvd, rank, size, "rc.d")

    # the world is fully usable afterwards (fresh names, full path)
    out = hvd.allreduce(np.full(5, float(rank + 1), np.float32),
                        average=False, name="rc.fresh")
    np.testing.assert_allclose(out, ssum)


def scenario_response_cache_hetero_spec(hvd, rank, size):
    """HOROVOD_CACHE_SPECULATIVE disagreeing across ranks (rank 1 has
    it off — set by the pytest wrapper) must stay CORRECT: speculation
    is per-cycle opportunistic, so the coordinator simply never sees a
    unanimous speculative cycle and every step rides the classic
    two-round cached path. Values stay exact, hits still accrue, and
    no rank ever completes a fused speculative cycle."""
    ssum = sum(range(1, size + 1))
    xs = [np.full(32, float(rank + 1) * (i + 1), np.float64)
          for i in range(6)]
    for _ in range(20):
        hs = [hvd.allreduce_async(x, average=False, name=f"hs.{i}")
              for i, x in enumerate(xs)]
        for i, h in enumerate(hs):
            np.testing.assert_allclose(hvd.synchronize(h),
                                       ssum * (i + 1.0))
    stats = _cache_runtime_stats(hvd)
    assert stats["cached_cycles"] > 0, (rank, stats)
    assert stats["spec_cycles"] == 0, (rank, stats)
    # and the spec-on ranks UNLEARN: after a few classically-answered
    # full grants the mask stops bidding, so the steady state is not
    # paying a wasted fused payload every cycle forever
    assert stats["spec_bids"] <= 8, (rank, stats)
    _assert_cache_coherent(hvd, rank, size, "hs.fp")


def scenario_native_steady(hvd, rank, size):
    """Zero-copy native steady cycle end to end (socket star; shm off
    and metrics armed by the pytest wrapper): a steady grouped-
    allreduce loop must (a) return exact sums every step, (b) complete
    steps through hvd_steady_worker/coord (native_steady_cycles
    advancing on every rank), (c) perform ZERO fallback byte-object
    copies on the data plane once steady (hvd_data_copies_total delta
    == 0 — the O(1)-allocations acceptance property), and (d) honor
    the aliasing contract: results returned at step k are never
    clobbered by later steps, and stay independently mutable."""
    from horovod_tpu import native as _nat

    ssum = sum(range(1, size + 1))
    xs = [np.full(128 + i, float(rank + 1) * (i + 1), np.float64)
          for i in range(8)]

    def step():
        hs = hvd.grouped_allreduce_async(xs, average=False, name="zc")
        return [np.asarray(hvd.synchronize(h)) for h in hs]

    for _ in range(4):
        step()
    hvd.barrier(name="zc.bar")
    s0 = _cache_runtime_stats(hvd)
    c0 = hvd.metrics()["local"].get("hvd_data_copies_total",
                                    {"v": 0.0})["v"]
    held = kept = None
    for it in range(25):
        res = step()
        for i, r in enumerate(res):
            np.testing.assert_allclose(r, ssum * (i + 1.0))
        if it == 5:
            kept = res                       # live views from step 5
            held = [r.copy() for r in res]   # their frozen values
    for a, b in zip(kept, held):
        np.testing.assert_array_equal(a, b)  # 19 later steps: intact
    kept[0] += 1000.0                        # outputs stay writable...
    res = step()
    for i, r in enumerate(res):              # ...and never feed back
        np.testing.assert_allclose(r, ssum * (i + 1.0))
    s1 = _cache_runtime_stats(hvd)
    c1 = hvd.metrics()["local"].get("hvd_data_copies_total",
                                    {"v": 0.0})["v"]
    assert s1["cached_cycles"] > s0["cached_cycles"] \
        or s1["spec_cycles"] > s0["spec_cycles"], (rank, s0, s1)
    native_on = (_nat.get() is not None
                 and os.environ.get("HOROVOD_TPU_ZERO_COPY", "1")
                 != "0")
    if os.environ.get("HOROVOD_TPU_SHM") == "0":
        # Socket star: the steady set rides the fused speculative
        # round; with the native core loaded, as ONE C call per step.
        assert s1["spec_cycles"] > s0["spec_cycles"], (rank, s0, s1)
        if native_on:
            assert s1["native_steady_cycles"] \
                > s0["native_steady_cycles"], (rank, s0, s1)
    if native_on:
        # The acceptance property: after warmup, steady steps perform
        # zero fallback byte-object copies on the data plane — on the
        # shm AND socket backends.
        assert c1 - c0 == 0, (rank, c0, c1)
    _assert_cache_coherent(hvd, rank, size, "zc.fp")


def scenario_native_hetero(hvd, rank, size):
    """Heterogeneous native worlds (the pytest wrapper turns the
    native core / zero-copy knob OFF on a subset of ranks): the
    CACHED_SPEC wire format is byte-identical whether a rank
    serializes in Python or sends iovecs from the arena, so mixed
    worlds must stay EXACT and still complete fused speculative
    cycles — and a native coordinator keeps its one-call steady loop
    even when some peers are pure Python."""
    from horovod_tpu import native as _nat

    ssum = sum(range(1, size + 1))
    xs = [np.full(96, float(rank + 1) * (i + 1), np.float64)
          for i in range(6)]
    for _ in range(4):
        hs = hvd.grouped_allreduce_async(xs, average=False, name="nh")
        for h in hs:
            hvd.synchronize(h)
    hvd.barrier(name="nh.bar")
    s0 = _cache_runtime_stats(hvd)
    for _ in range(20):
        hs = hvd.grouped_allreduce_async(xs, average=False, name="nh")
        for i, h in enumerate(hs):
            np.testing.assert_allclose(hvd.synchronize(h),
                                       ssum * (i + 1.0))
    s1 = _cache_runtime_stats(hvd)
    assert s1["spec_cycles"] > s0["spec_cycles"], (rank, s0, s1)
    if rank == 0 and _nat.get() is not None \
            and os.environ.get("HOROVOD_TPU_ZERO_COPY", "1") != "0":
        # the coordinator runs natively even over pure-Python peers
        assert s1["native_steady_cycles"] > s0["native_steady_cycles"], \
            (rank, s0, s1)
    _assert_cache_coherent(hvd, rank, size, "nh.fp")


def scenario_overlap_steady(hvd, rank, size):
    """Overlap tier end to end (HOROVOD_OVERLAP_* armed by the pytest
    wrapper): a bucketed grouped-allreduce training loop must
    (a) return exact sums every step, (b) split each step into
    multiple buckets (hvd_overlap_buckets_total advancing) that each
    learn their own steady mask, (c) complete steady cycles through
    the in-flight overlap runner (overlap_cycles advancing), and
    (d) preserve the zero-copy property: hvd_data_copies_total does
    not move once steady. With HOROVOD_COMPRESSION=bf16 the values
    here are small integers (exactly representable), so compression
    (and the chunked native send with a small
    HOROVOD_OVERLAP_CHUNK_BYTES) keeps the asserts exact."""
    from horovod_tpu import native as _nat
    from horovod_tpu.common import basics as _b

    ssum = sum(range(1, size + 1))
    xs = [np.full(192 + 16 * i, float(rank + 1) * (i + 1), np.float32)
          for i in range(16)]

    def step():
        hs = hvd.grouped_allreduce_async(xs, average=False, name="ov")
        return [np.asarray(hvd.synchronize(h)) for h in hs]

    for _ in range(8):
        step()  # warmup: every bucket learns its steady mask
    hvd.barrier(name="ov.bar")
    s0 = _cache_runtime_stats(hvd)
    c0 = hvd.metrics()["local"].get("hvd_data_copies_total",
                                    {"v": 0.0})["v"]
    for it in range(25):
        res = step()
        for i, r in enumerate(res):
            np.testing.assert_allclose(r, ssum * (i + 1.0))
    s1 = _cache_runtime_stats(hvd)
    c1 = hvd.metrics()["local"].get("hvd_data_copies_total",
                                    {"v": 0.0})["v"]
    rt = _b.runtime()
    k = int(os.environ.get("HOROVOD_OVERLAP_BUCKETS", "0"))
    if k > 1:
        # bucketed dispatch engaged: the submission really split
        m = hvd.metrics()["local"]
        assert m.get("hvd_overlap_buckets_total",
                     {"v": 0.0})["v"] > 0, m
        # each bucket holds its own steady mask
        assert len(rt._steady) >= 2, (rank, len(rt._steady))
    native_on = (_nat.get() is not None
                 and os.environ.get("HOROVOD_TPU_ZERO_COPY", "1")
                 != "0")
    if native_on and int(os.environ.get(
            "HOROVOD_OVERLAP_INFLIGHT", "0")) > 0:
        # in-flight cycles engaged and zero-copy preserved
        assert s1["overlap_cycles"] > s0["overlap_cycles"], (
            rank, s0, s1)
        assert c1 - c0 == 0, (rank, c0, c1)
    assert s1["cached_cycles"] > s0["cached_cycles"] \
        or s1["spec_cycles"] > s0["spec_cycles"], (rank, s0, s1)
    _assert_cache_coherent(hvd, rank, size, "ov.fp")


def scenario_overlap_bitexact(hvd, rank, size):
    """Bucketed training must be BIT-exact vs an unbucketed replay:
    run the same deterministic step stream twice in one world — first
    with the wrapper-armed bucket knobs, then with bucketing turned
    off on every rank at the same point — and require bitwise-equal
    outputs. Values are rounding-sensitive f32 fractions, so any
    reduction-order change WOULD show: bucketing only moves fused
    batch boundaries, never the per-element rank-ascending sum."""
    from horovod_tpu.common import basics as _b

    xs = [np.full(128 + 8 * i, 0.1 * (rank + 1) * (i + 1), np.float32)
          for i in range(12)]

    def phase(tag, steps=10):
        outs = None
        for _ in range(steps):
            hs = hvd.grouped_allreduce_async(xs, average=False,
                                             name=f"bx.{tag}")
            outs = [np.asarray(hvd.synchronize(h)) for h in hs]
        return outs

    a = phase("bucketed")
    hvd.barrier(name="bx.bar")
    # Same point on every rank: later submissions stop bucketing.
    _b.runtime().config.overlap_buckets = 0
    _b.runtime().config.overlap_bucket_bytes = 0
    b = phase("flat")
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    _assert_cache_coherent(hvd, rank, size, "bx.fp")


def scenario_overlap_hetero(hvd, rank, size):
    """Heterogeneous HOROVOD_OVERLAP_* knobs (set per-rank by the
    pytest wrapper): ranks disagree on bucket count and in-flight
    depth, so per-cycle hit masks differ — grants degrade to the
    intersection, speculation backs off where peers answer
    classically, and the world must stay EXACT and cache-coherent
    (degrade-to-synchronous, never diverge)."""
    ssum = sum(range(1, size + 1))
    xs = [np.full(160 + 8 * i, float(rank + 1) * (i + 1), np.float32)
          for i in range(12)]
    for _ in range(20):
        hs = hvd.grouped_allreduce_async(xs, average=False, name="oh")
        res = [np.asarray(hvd.synchronize(h)) for h in hs]
        for i, r in enumerate(res):
            np.testing.assert_allclose(r, ssum * (i + 1.0))
    _assert_cache_coherent(hvd, rank, size, "oh.fp")


def scenario_overlap_sigkill(hvd, rank, size):
    """SIGKILL a rank while buckets are IN FLIGHT on the overlap
    runner (fault spec fires at an op index deep in bucketed steady
    state): survivors must raise WorldAbortedError naming the dead
    rank within the heartbeat deadline — the PR 2 fail-fast invariant
    holds when the native cycle runs on the completion thread."""
    import time
    from horovod_tpu.common.status import WorldAbortedError

    victim = 1
    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    xs = [np.full(128 + 8 * i, float(rank + 1), np.float32)
          for i in range(16)]
    t0 = time.monotonic()
    aborted = None
    while True:
        try:
            hs = hvd.grouped_allreduce_async(xs, average=False,
                                             name="ok.steady")
            for h in hs:
                hvd.synchronize(h)
        except WorldAbortedError as e:
            aborted = e
            break
        assert time.monotonic() - t0 < deadline, (
            f"rank {rank}: collectives kept succeeding {deadline}s "
            f"after the fault")
    assert aborted.origin_rank == victim, (rank, str(aborted))
    assert f"rank {victim}" in str(aborted), str(aborted)
    assert time.monotonic() - t0 < deadline
    stats = _cache_runtime_stats(hvd)
    assert stats["cached_cycles"] >= 5 or stats["spec_cycles"] >= 5, \
        stats
    hvd.shutdown()


def scenario_overlap_sever(hvd, rank, size):
    """Severed control link mid-overlapped-cycle: rank 1's upward
    channel closes while the overlap runner drives native cycles;
    survivors must abort with a structured WorldAbortedError within
    the deadline (the runner's parked transport error feeds the same
    world-convergent blame path as the synchronous one)."""
    import time
    from horovod_tpu.common.status import WorldAbortedError

    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    xs = [np.full(128, float(rank + 1), np.float32) for _ in range(8)]
    t0 = time.monotonic()
    while True:
        try:
            hs = hvd.grouped_allreduce_async(xs, average=False,
                                             name="os.steady")
            for h in hs:
                hvd.synchronize(h)
        except WorldAbortedError as e:
            assert e.origin_rank >= -1, str(e)
            break
        assert time.monotonic() - t0 < deadline, (
            f"rank {rank}: collectives kept succeeding {deadline}s "
            f"after the sever")
    assert time.monotonic() - t0 < deadline
    hvd.shutdown()


def scenario_abort_sigkill_native_steady(hvd, rank, size):
    """SIGKILL a rank squarely mid-NATIVE-steady-cycle (fault spec
    fires at an op index reached deep in zero-copy steady state, so
    survivors are blocked inside hvd_steady_worker/coord when the
    victim dies): the C loop must honor the armed recv deadlines and
    surface the PR 2 fail-fast invariant — every survivor raises
    WorldAbortedError naming the dead rank within the heartbeat
    deadline."""
    import time
    from horovod_tpu.common.status import WorldAbortedError

    victim = 1
    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    x = np.full(256, float(rank + 1), np.float64)
    t0 = time.monotonic()
    aborted = None
    while True:
        try:
            hvd.allreduce(x, average=False, name="zk.steady")
        except WorldAbortedError as e:
            aborted = e
            break
        assert time.monotonic() - t0 < deadline, (
            f"rank {rank}: collectives kept succeeding {deadline}s "
            f"after the fault")
    assert aborted.origin_rank == victim, (rank, str(aborted))
    assert f"rank {victim}" in str(aborted), str(aborted)
    assert time.monotonic() - t0 < deadline
    stats = _cache_runtime_stats(hvd)
    from horovod_tpu import native as _nat
    if _nat.get() is not None:
        # the kill really did land in zero-copy steady state
        assert stats["native_steady_cycles"] >= 5, stats
    try:
        hvd.allreduce(x, average=False, name="zk.post")
        raise AssertionError("enqueue after world abort must fail")
    except WorldAbortedError as e:
        assert e.origin_rank == victim, str(e)
    hvd.shutdown()


def scenario_abort_sever_native_steady(hvd, rank, size):
    """Severed control link mid-native-steady-cycle (fault injection
    closes rank 1's upward channel at a deep cycle index): survivors
    must abort with a structured WorldAbortedError within the
    deadline — the native loop's transport errors feed the same
    world-convergent blame path as the Python one."""
    import time
    from horovod_tpu.common.status import WorldAbortedError

    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    x = np.full(256, float(rank + 1), np.float64)
    t0 = time.monotonic()
    aborted = None
    while True:
        try:
            hvd.allreduce(x, average=False, name="zs.steady")
        except WorldAbortedError as e:
            aborted = e
            break
        assert time.monotonic() - t0 < deadline, (
            f"rank {rank}: collectives kept succeeding {deadline}s "
            f"after the sever")
    assert aborted.origin_rank >= -1, str(aborted)
    assert time.monotonic() - t0 < deadline
    hvd.shutdown()


def scenario_response_cache_eviction(hvd, rank, size):
    """Capacity eviction under a tiny HOROVOD_CACHE_CAPACITY (set by
    the pytest wrapper): cycling through more distinct tensors than
    slots keeps evicting in LRU order — on every rank identically —
    and values stay exact throughout, including when an evicted name
    comes back (miss -> full renegotiation -> re-cached)."""
    cap = int(os.environ["HOROVOD_CACHE_CAPACITY"])
    ssum = sum(range(1, size + 1))
    n_names = cap * 3
    for wave in range(3):
        for i in range(n_names):
            out = hvd.allreduce(
                np.full(16, float(rank + 1) * (i + 1), np.float64),
                average=False, name=f"ev.{i}")
            np.testing.assert_allclose(out, ssum * (i + 1.0))
        _assert_cache_coherent(hvd, rank, size, f"ev.fp{wave}")
    stats = _cache_runtime_stats(hvd)
    assert stats["entries"] <= cap, stats
    # steady reuse of a WORKING set under capacity still gets hits
    s0 = _cache_runtime_stats(hvd)
    for _ in range(10):
        for i in range(max(1, cap // 2)):
            hvd.allreduce(np.full(8, float(rank + 1), np.float64),
                          average=False, name=f"ws.{i}")
    s1 = _cache_runtime_stats(hvd)
    assert s1["hits"] > s0["hits"], (s0, s1)
    _assert_cache_coherent(hvd, rank, size, "ev.fin")


def scenario_abort_sigkill_cached(hvd, rank, size):
    """SIGKILL a rank squarely mid-CACHED-cycle: fault injection fires
    at an op index reached deep in bitmask steady state, so the
    survivors are blocked in a bits-frame gather when the victim dies.
    They must still raise WorldAbortedError naming the dead rank within
    the heartbeat deadline (the PR 2 fail-fast invariant holds on the
    fast path), and handles enqueued afterwards must fail the same
    structured way."""
    import time
    from horovod_tpu.common.status import WorldAbortedError

    victim = 1
    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    x = np.full(64, float(rank + 1), np.float32)
    t0 = time.monotonic()
    i = 0
    aborted = None
    while True:
        try:
            # SAME name every iteration: after the first op the cycle
            # is pure bitmask — the fault (op=40) lands mid-fast-path
            hvd.allreduce(x, average=False, name="ck.steady")
        except WorldAbortedError as e:
            aborted = e
            break
        i += 1
        assert time.monotonic() - t0 < deadline, (
            f"rank {rank}: collectives kept succeeding {deadline}s "
            f"after the fault")
    assert aborted.origin_rank == victim, (rank, str(aborted))
    assert f"rank {victim}" in str(aborted), str(aborted)
    assert time.monotonic() - t0 < deadline
    # the kill really did land in cached steady state
    stats = _cache_runtime_stats(hvd)
    assert stats["cached_cycles"] >= 10, stats
    try:
        hvd.allreduce(x, average=False, name="ck.post")
        raise AssertionError("enqueue after world abort must fail")
    except WorldAbortedError as e:
        assert e.origin_rank == victim, str(e)
    hvd.shutdown()


def scenario_cache_byte_budget(hvd, rank, size):
    """Control-plane byte-budget regression guard: in bitmask steady
    state a cycle must move O(capacity/8) control bytes per rank —
    asserted through a counting wrapper on Channel.send/recv that
    tallies ONLY the control tags (TAG_REQUESTS/TAG_RESPONSES; data
    payloads and PINGs ride other tags). A regression that quietly
    re-serializes Request lists every cycle trips the per-cycle
    budget by an order of magnitude. The pytest wrapper disables
    HOROVOD_CACHE_SPECULATIVE: fused speculative frames deliberately
    carry the batch's tensor data on the request tag (that is the
    point — one round for grant AND data), so the mask-path budget is
    only measurable with speculation off."""
    from horovod_tpu.common import controller as _ctl
    from horovod_tpu.common import network as _net

    counts = {"bytes": 0}
    ctrl_tags = (_ctl.TAG_REQUESTS, _ctl.TAG_RESPONSES)
    orig_send, orig_recv = _net.Channel.send, _net.Channel.recv

    def send(self, payload, tag=0):
        if tag in ctrl_tags:
            counts["bytes"] += len(_net.as_byte_view(payload))
        return orig_send(self, payload, tag)

    def recv(self):
        tag, data = orig_recv(self)
        if tag in ctrl_tags:
            counts["bytes"] += len(data)
        return tag, data

    _net.Channel.send, _net.Channel.recv = send, recv
    hvd.init()
    from horovod_tpu.common import basics as _b
    rt = _b.runtime()

    capacity = int(os.environ["HOROVOD_CACHE_CAPACITY"])
    ssum = sum(range(1, size + 1))
    names = [f"bb.{i}" for i in range(16)]
    xs = [np.full(64, float(rank + 1) * (i + 1), np.float64)
          for i in range(16)]

    def step():
        hs = [hvd.allreduce_async(x, average=False, name=nm)
              for x, nm in zip(xs, names)]
        for h in hs:
            hvd.synchronize(h)

    for _ in range(5):
        step()
    hvd.barrier(name="bb.bar")
    bytes0, cycles0 = counts["bytes"], rt._cycle_count
    for _ in range(50):
        step()
    bytes1, cycles1 = counts["bytes"], rt._cycle_count
    stats = rt.negotiation_cache_stats()
    d_cycles = max(1, cycles1 - cycles0)
    per_cycle = (bytes1 - bytes0) / d_cycles
    # Worker budget: one bitmask request frame + one bitmask response
    # frame per cycle — two masks each plus fixed headers. The full
    # path for 16 tensors moves well over 1 KB per cycle.
    budget = 2 * ((capacity + 7) // 8) + 160
    if rank != 0:
        # rank 0's per-cycle frames ride the native fan-out, not
        # Channel.send/recv — the budget is asserted on workers, whose
        # Python channel is the steady-state path being guarded.
        assert per_cycle <= budget, (
            f"rank {rank}: steady-state control plane moved "
            f"{per_cycle:.0f} B/cycle (budget {budget} B with "
            f"HOROVOD_CACHE_CAPACITY={capacity}) — fast-path "
            f"regression")
    assert stats["hit_rate"] >= 0.95, stats
    # correctness spot check after all the counting
    out = hvd.allreduce(np.full(8, float(rank + 1), np.float64),
                        average=False, name="bb.check")
    np.testing.assert_allclose(out, ssum)
    _net.Channel.send, _net.Channel.recv = orig_send, orig_recv


scenario_cache_byte_budget.no_auto_init = True


def scenario_metrics_world(hvd, rank, size):
    """World-aggregated metrics plane end to end (HOROVOD_TPU_METRICS
    + interval + ephemeral port set by the pytest wrapper): a steady
    allreduce loop runs, every rank allgathers its LOCAL
    hvd_bytes_allreduced_total, and rank 0 polls its control-tree
    world aggregate until it equals the per-rank sum exactly — then
    scrapes the live Prometheus endpoint and asserts the text view
    agrees. Runs identically across shm / socket / hierarchical
    worlds (the hier wrapper proves local roots fold their host into
    one METRICS frame without losing counts)."""
    import time
    import urllib.request

    ssum = sum(range(1, size + 1))
    x = np.full(256, float(rank + 1), np.float64)
    steps = 20
    for _ in range(steps):
        out = hvd.allreduce(x, average=False, name="mw.steady")
        np.testing.assert_allclose(out, ssum)

    view = hvd.metrics()
    assert view["enabled"], view
    local = view["local"]["hvd_bytes_allreduced_total"]["v"]
    assert local == steps * x.nbytes, (rank, local, steps * x.nbytes)
    # Share the true per-rank totals over the data plane (allgather
    # moves bytes too, but not ALLREDUCE bytes — the counter under
    # test stays frozen from here on).
    got = np.asarray(hvd.allgather(
        np.asarray([[local]], np.float64), name="mw.locals"))
    expected_world = float(got.sum())

    if rank == 0:
        port = view["http_port"]
        assert port and port > 0, view
        deadline = time.monotonic() + 30.0
        world_v = None
        while time.monotonic() < deadline:
            world = hvd.metrics()["world"]
            world_v = world.get("hvd_bytes_allreduced_total",
                                {}).get("v")
            reporting = world.get("hvd_ranks_reporting", {}).get("v")
            if world_v == expected_world and reporting == size:
                break
            time.sleep(0.1)
        assert world_v == expected_world, (world_v, expected_world)
        # the live Prometheus endpoint must agree with the API view
        txt = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        value_line = [l for l in txt.splitlines()
                      if l.startswith("hvd_bytes_allreduced_total ")]
        assert value_line, txt[:2000]
        assert float(value_line[0].split()[1]) == expected_world, \
            (value_line, expected_world)
        assert "# TYPE hvd_bytes_allreduced_total counter" in txt
        assert "hvd_negotiation_seconds_count" in txt
        assert "hvd_cycle_seconds_bucket" in txt
        if size > 1:
            assert "hvd_peer_heartbeat_age_seconds" in txt
    # hold the world together until rank 0 finished polling/scraping
    hvd.barrier(name="mw.done")


def scenario_metrics_sigkill(hvd, rank, size):
    """SIGKILL a rank mid-run WHILE rank 0 is being scraped (fault
    spec + metrics env set by the pytest wrapper): the metrics plane —
    out-of-band frames on the very channels the abort protocol
    watches — must not mask PR 2's fail-fast invariant. Survivors
    raise WorldAbortedError naming the dead rank within the heartbeat
    deadline, with a scraper thread hammering /metrics throughout."""
    import threading
    import time
    import urllib.request
    from horovod_tpu.common.status import WorldAbortedError

    victim = 1
    deadline_s = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    scrapes = []
    stop = threading.Event()
    if rank == 0:
        port = hvd.metrics()["http_port"]
        assert port and port > 0

        def _scrape_loop():
            while not stop.is_set():
                try:
                    txt = urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=2).read().decode()
                    scrapes.append("hvd_cycles_total" in txt)
                except Exception:
                    pass
                time.sleep(0.05)

        t = threading.Thread(target=_scrape_loop, daemon=True)
        t.start()

    x = np.full(64, float(rank + 1), np.float32)
    t0 = time.monotonic()
    aborted = None
    while True:
        try:
            hvd.allreduce(x, average=False, name="ms.steady")
        except WorldAbortedError as e:
            aborted = e
            break
        assert time.monotonic() - t0 < deadline_s, (
            f"rank {rank}: collectives kept succeeding {deadline_s}s "
            f"after the fault")
    assert aborted.origin_rank == victim, (rank, str(aborted))
    assert f"rank {victim}" in str(aborted), str(aborted)
    if rank == 0:
        stop.set()
        assert scrapes and any(scrapes), \
            "no successful scrape while the world was live"
    try:
        hvd.allreduce(x, average=False, name="ms.post")
        raise AssertionError("enqueue after world abort must fail")
    except WorldAbortedError as e:
        assert e.origin_rank == victim, str(e)
    hvd.shutdown()


def scenario_trace_world(hvd, rank, size):
    """World trace plane e2e (ISSUE 11; env set by the pytest
    wrapper: HOROVOD_TPU_TRACE=<merged path>, metrics armed, short
    ping/trace intervals, speculation off so every recv rides the
    Python paths where PINGs close the clock loop, and a repeating
    ``delay`` fault making rank 2 a sustained straggler). A steady
    loop runs; rank 0 then asserts the straggler attribution NAMES
    rank 2 (max arrival lag strictly dominant + last-arriver counter
    advanced), the skew histogram observed every gather, and the
    clock-sync table closed at least one NTP loop. The wrapper
    additionally validates the merged catapult file."""
    import time

    from horovod_tpu.common import basics as _b
    from horovod_tpu.common import trace as _htrace

    ssum = float(sum(range(1, size + 1)))
    x = np.full(256, float(rank + 1), np.float64)
    for _ in range(60):
        out = hvd.allreduce(x, average=False, name="tw.g")
        np.testing.assert_allclose(np.asarray(out)[:1], ssum)
        time.sleep(0.02)
    # let one more publish interval pass so tail spans/echoes ship
    time.sleep(0.7)
    hvd.barrier(name="tw.flush")
    if rank == 0:
        rt = _b.runtime()
        st = rt._straggler
        assert st is not None
        line = st.report_line()
        assert line, "straggler window empty after 60 gathers"
        local = hvd.metrics()["local"]

        def metric(name, field="v", default=0.0):
            return local.get(name, {}).get(field, default)

        lag2 = metric('hvd_arrival_lag_seconds{peer="2"}')
        # the injected 250ms delay shows in rank 2's worst lag...
        assert lag2 >= 0.15, (lag2, local)
        # ...but a loaded host can hand a healthy rank ONE comparable
        # scheduling stall, so the attribution signal is the
        # last-arriver COUNTER (sustained, 10 repeated delays), which
        # must name rank 2 over every healthy peer — and the report
        # line is that attribution
        c2 = metric('hvd_last_arriver_total{peer="2"}')
        assert c2 >= 10, local
        for r in range(1, size):
            if r != 2:
                assert c2 > metric(
                    f'hvd_last_arriver_total{{peer="{r}"}}'), \
                    (r, local)
        assert "rank 2 last-arriver" in line, line
        skew = local.get("hvd_cycle_skew_seconds", {})
        assert skew.get("count", 0) >= 30, skew
        # build identity rides the same registry
        assert any(n.startswith("hvd_build_info{") for n in local), \
            sorted(local)[:20]
        # the piggybacked NTP exchange closed: offsets exist and are
        # sane for same-host processes
        offs = _htrace.clock().offsets()
        assert offs, "no clock-sync echo ever closed"
        for r, (off, rtt) in offs.items():
            assert abs(off) < 1.0 and 0.0 <= rtt < 1.0, (r, off, rtt)
    hvd.barrier(name="tw.done")


def scenario_trace_native_arrivals(hvd, rank, size):
    """Arrival stamps must cover the native steady gather
    (hvd_steady_coord): metrics armed, socket star + speculation +
    zero-copy on — the steady loop collapses into one-call native
    cycles, and the coordinator's skew histogram must keep observing
    every gather while they run."""
    from horovod_tpu import native as _nat
    from horovod_tpu.common import basics as _b

    ssum = float(sum(range(1, size + 1)))
    x = np.full(1024, float(rank + 1), np.float32)
    for _ in range(40):
        out = hvd.allreduce(x, average=False, name="tn.g")
    np.testing.assert_allclose(np.asarray(out)[:1], ssum)
    hvd.barrier(name="tn.flush")
    rt = _b.runtime()
    if rank == 0:
        stats = rt.negotiation_cache_stats()
        local = hvd.metrics()["local"]
        skew = local.get("hvd_cycle_skew_seconds", {})
        assert skew.get("count", 0) > 0, (skew, stats)
        if _nat.get() is not None:
            # the C loop carried the world — and the skew histogram
            # kept advancing through it (hvd_steady_coord stamps)
            assert stats["native_steady_cycles"] >= 5, stats
            assert skew["count"] >= stats["native_steady_cycles"], \
                (skew, stats)
        # exactly one last-arriver is charged per stamped gather
        last_total = sum(
            rec.get("v", 0) for name, rec in local.items()
            if name.startswith("hvd_last_arriver_total"))
        assert last_total == skew["count"], (last_total, skew)
    hvd.barrier(name="tn.done")


def scenario_flight_sigkill(hvd, rank, size):
    """SIGKILL mid-steady-cycle (fault spec + flight dir set by the
    wrapper): every survivor must (a) raise WorldAbortedError naming
    the dead rank — the PR 2 invariant — and (b) find its OWN
    flight-recorder postmortem dump on disk, written by the abort
    path with no profiling armed, naming the dead rank and containing
    the final cycles."""
    import json as _json
    import time

    from horovod_tpu.common.status import WorldAbortedError

    victim = 2
    deadline_s = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    x = np.full(512, float(rank + 1), np.float32)
    t0 = time.monotonic()
    aborted = None
    while True:
        try:
            hvd.allreduce(x, average=False, name="fs.g")
        except WorldAbortedError as e:
            aborted = e
            break
        assert time.monotonic() - t0 < deadline_s, (
            f"rank {rank}: collectives kept succeeding {deadline_s}s "
            f"after the fault")
    assert aborted.origin_rank == victim, (rank, str(aborted))
    assert f"rank {victim}" in str(aborted), str(aborted)
    # The abort handler dumps on the background thread; the user
    # thread may observe the error first — wait briefly.
    path = os.path.join(os.environ["HOROVOD_TPU_FLIGHT_DIR"],
                        f"hvd-flight-rank{rank}.pid{os.getpid()}"
                        f".jsonl")
    deadline = time.monotonic() + 15.0
    lines = []
    while time.monotonic() < deadline:
        try:
            lines = [_json.loads(line) for line in open(path)]
        except (OSError, ValueError):
            lines = []  # not there yet, or caught mid-write
        # the header's "events" count says when the block is complete
        if lines and len(lines) >= 1 + lines[0].get("events", 0):
            break
        time.sleep(0.05)
    assert lines and len(lines) >= 1 + lines[0].get("events", 0), \
        f"no complete flight dump at {path}"
    header, events = lines[0], lines[1:]
    assert header["flight"] == 1 and header["rank"] == rank
    assert header["origin"] == victim, header
    assert f"rank {victim}" in header["cause"], header
    assert set(header["build"]) == {"version", "native", "knobs",
                                    "flags"}
    cycles = [e["cycle"] for e in events if e["ev"] == "cycle"]
    assert cycles and max(cycles) >= 10, (
        "dump does not contain the final cycles", cycles[-5:])
    assert any(e["ev"] == "abort" and e.get("arg") == victim
               for e in events), events[-5:]
    hvd.shutdown()


def scenario_kitchen_sink(hvd, rank, size):
    """Every auxiliary subsystem enabled at once — autotune (+log),
    timeline (+cycle marks), hierarchical shm over a fake 2-host
    topology, stall checker armed — under mixed per-rank-shuffled
    traffic with a mid-stream coordinator ERROR and recovery. The
    artifacts (timeline JSON, autotune CSV) are verified by the
    spawning test after shutdown."""
    from horovod_tpu.common import basics as _b
    from horovod_tpu.common.status import HorovodInternalError

    rt = _b.runtime()
    assert rt.parameter_manager is not None, "autotune must be active"
    assert rt.timeline.enabled or rank != 0

    ssum = sum(range(1, size + 1))
    rng = np.random.RandomState(77 + rank)  # per-rank order!
    for round_ in range(20):
        jobs = [("ar", i) for i in range(4)] + \
               [("bc", i) for i in range(4)] + \
               [("ag", i) for i in range(2)] + \
               [("rs", i) for i in range(2)]
        handles = {}
        for idx in rng.permutation(len(jobs)):
            kind, i = jobs[idx]
            tag = f"ks{round_}.{kind}{i}"
            if kind == "ar":
                handles[(kind, i)] = hvd.allreduce_async(
                    np.full(300 + i, float(rank + 1) * (i + 1),
                            np.float64), average=False, name=tag)
            elif kind == "bc":
                handles[(kind, i)] = hvd.broadcast_async(
                    np.full(16, float(rank * 10 + i), np.float32),
                    root_rank=i % size, name=tag)
            elif kind == "ag":
                handles[(kind, i)] = hvd.allgather_async(
                    np.full((rank + 1, 3), float(rank + i), np.float32),
                    name=tag)
            else:
                handles[(kind, i)] = hvd.reducescatter_async(
                    np.arange(size * 4, dtype=np.float64) + rank,
                    name=tag)
        for (kind, i), h in handles.items():
            out = np.asarray(hvd.synchronize(h))
            if kind == "ar":
                np.testing.assert_allclose(
                    out, np.full(300 + i, ssum * (i + 1)))
            elif kind == "bc":
                np.testing.assert_allclose(
                    out, float((i % size) * 10 + i))
            elif kind == "ag":
                assert out.shape == (sum(r + 1 for r in range(size)), 3)
            else:
                base = size * np.arange(size * 4) + sum(range(size))
                np.testing.assert_allclose(
                    out, base[rank * 4:(rank + 1) * 4])

        if round_ == 3:
            # coordinator ERROR mid-storm: mismatched shapes...
            shape = (4, 5) if rank == 0 else (4, 6)
            try:
                hvd.allreduce(np.ones(shape, np.float32), name="ks.bad")
            except HorovodInternalError:
                pass
            else:
                raise AssertionError("expected HorovodInternalError")
            # ...and the world keeps negotiating afterwards
            np.testing.assert_allclose(
                hvd.allreduce(np.ones(5, np.float32), average=False,
                              name="ks.recover"),
                size * np.ones(5))

    # Pump the autotuner to its first LOGGED sample. The discrete
    # (algorithm x wire) sweep consumes a topology-dependent number of
    # busy cycles before the Bayesian phase appends CSV row 1, and
    # cycle coalescing makes "N rounds" a nondeterministic cycle
    # count — so drive small allreduces until the coordinator's log
    # shows a data row, agreeing on the verdict through the reduction
    # itself (every rank must leave the loop on the same cycle).
    log_path = os.environ.get("HOROVOD_AUTOTUNE_LOG", "")
    for pump in range(600):
        hvd.allreduce(np.full(64, 1.0, np.float64), average=False,
                      name=f"ks.pump{pump}")
        done = 0.0
        if rank == 0 and log_path:
            try:
                with open(log_path) as f:
                    rows = [ln for ln in f.read().splitlines()
                            if ln.strip()]
                done = float(len(rows) >= 2)
            except OSError:
                done = 0.0
        agreed = np.asarray(hvd.allreduce(
            np.full(1, done, np.float64), average=False,
            name=f"ks.pumpchk{pump}"))
        if agreed[0] > 0:
            break
    else:
        raise AssertionError("autotune never logged a sample row")

    hvd.barrier(name="ks.done")


def scenario_bf16_host_path(hvd, rank, size):
    """bfloat16 — the TPU-native wire/accumulate dtype — through the
    host collectives (native sum kernel or numpy/ml_dtypes fallback)."""
    try:
        import ml_dtypes
    except ImportError:
        return  # numpy-only install: nothing to test
    # careful: bf16 * python-int silently promotes to f32 (ml_dtypes
    # weak promotion) — cast LAST so the wire dtype really is bf16
    x = np.full(64, float(rank + 1)).astype(ml_dtypes.bfloat16)
    out = hvd.allreduce(x, average=False, name="bf.ar")
    assert np.asarray(out).dtype == ml_dtypes.bfloat16, \
        np.asarray(out).dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               sum(range(1, size + 1)))
    g = hvd.allgather(
        np.full((2, 3), float(rank)).astype(ml_dtypes.bfloat16),
        name="bf.ag")
    assert np.asarray(g).shape == (2 * size, 3)
    assert np.asarray(g).dtype == ml_dtypes.bfloat16
    b = hvd.broadcast(np.full(4, float(rank)).astype(ml_dtypes.bfloat16),
                      root_rank=1, name="bf.bc")
    np.testing.assert_allclose(np.asarray(b, np.float32), 1.0)


def _metric_value(hvd, name: str) -> float:
    rec = hvd.metrics()["local"].get(name)
    if rec is None:
        return 0.0
    return rec["v"] if "v" in rec else rec.get("count", 0)


def scenario_compression_steady_zero_copy(hvd, rank, size):
    """Compressed steady state end to end (HOROVOD_COMPRESSION=bf16 +
    metrics armed + shm/ring off by the pytest wrapper): a steady
    grouped-allreduce loop of bf16-exact values must (a) stay exact,
    (b) keep riding the fused speculative round (and the native
    zero-copy cycle when the library is loaded) with the COMPRESSED
    payload — hvd_data_copies_total delta stays 0, proving the
    ISSUE 9 contract that compression composes with the PR 6 plane —
    and (c) report wire bytes actually saved."""
    from horovod_tpu.common import basics as _b
    from horovod_tpu import native as _nat

    ssum = sum(range(1, size + 1))
    # Small integers: exactly representable in bf16, so the compressed
    # world is assertable bit-for-bit.
    xs = [np.full(256 + i, float(rank + 1) * (i + 1), np.float32)
          for i in range(6)]

    def step():
        hs = hvd.grouped_allreduce_async(xs, average=False, name="cz")
        for i, h in enumerate(hs):
            np.testing.assert_allclose(np.asarray(hvd.synchronize(h)),
                                       ssum * (i + 1.0))

    for _ in range(5):
        step()
    hvd.barrier(name="cz.bar")
    rt = _b.runtime()
    s0 = rt.negotiation_cache_stats()
    copies0 = _metric_value(hvd, "hvd_data_copies_total")
    saved0 = _metric_value(hvd, "hvd_wire_bytes_saved_total")
    for _ in range(25):
        step()
    s1 = rt.negotiation_cache_stats()
    copies1 = _metric_value(hvd, "hvd_data_copies_total")
    saved1 = _metric_value(hvd, "hvd_wire_bytes_saved_total")
    assert s1["spec_cycles"] > s0["spec_cycles"], (rank, s0, s1)
    if _nat.get() is not None:
        assert s1["native_steady_cycles"] > s0["native_steady_cycles"], \
            (rank, s0, s1)
    assert copies1 - copies0 == 0, (rank, copies0, copies1)
    assert saved1 > saved0, (rank, saved0, saved1)
    # bf16 halves the payload: per fused step the saving is half the
    # uncompressed fused bytes
    per_step = sum(x.nbytes for x in xs) // 2
    assert saved1 - saved0 >= 20 * per_step, (rank, saved0, saved1)


def scenario_compression_hetero(hvd, rank, size):
    """Heterogeneous compression knobs (the pytest wrapper proposes
    bf16 on ONE rank only, or on all — same scenario both ways): the
    coordinator resolves every batch to the common denominator, and a
    world whose verdict is `none` must be BIT-EXACT with a fresh
    all-none world replaying the same submissions — the wrapper runs
    both worlds and compares the files byte-for-byte."""
    rng = np.random.RandomState(1000 + rank)
    outs = []
    for step in range(8):
        x = rng.randn(777).astype(np.float32)
        outs.append(np.asarray(
            hvd.allreduce(x, average=False, name=f"hx.{step}")))
    g = hvd.allgather(np.asarray([[float(rank)]], np.float32),
                      name="hx.ag")
    outs.append(np.asarray(g))
    out_path = os.environ.get("HVD_COMPRESSION_OUT")
    if rank == 0 and out_path:
        np.save(out_path, np.concatenate([o.reshape(-1) for o in outs]))
    # a bf16-proposing rank in a mixed world must see an uncompressed
    # verdict: zero wire bytes saved anywhere
    if os.environ.get("HOROVOD_TPU_METRICS") == "1":
        assert _metric_value(hvd, "hvd_wire_bytes_saved_total") == 0, \
            rank


def scenario_twolevel_allreduce(hvd, rank, size):
    """Two-level hierarchical allreduce on a (fake) multi-host world
    (HOROVOD_TWO_LEVEL=1 + HOROVOD_COMPRESSION=bf16 + metrics armed by
    the wrapper): intra-host shm reduce, cross-host ring among local
    roots, intra-host shm broadcast. Values are bf16-exact so the
    compressed cross leg is assertable exactly; the per-algorithm op
    counter proves the plane actually carried the batches."""
    ssum = sum(range(1, size + 1))
    for step in range(6):
        x = np.full(2048, float(rank + 1), np.float32)
        out = hvd.allreduce(x, average=False, name=f"tl.{step}")
        np.testing.assert_allclose(np.asarray(out), ssum)
    # a bandwidth-bound op through the same plane
    big = np.full(1 << 18, float(rank + 1), np.float32)
    out = hvd.allreduce(big, average=False, name="tl.big")
    np.testing.assert_allclose(np.asarray(out), ssum)
    # non-allreduce collectives keep their own planes alongside
    g = hvd.allgather(np.full((2, 2), float(rank), np.float32),
                      name="tl.ag")
    assert np.asarray(g).shape == (2 * size, 2)
    assert _metric_value(hvd, "hvd_ops_twolevel_total") >= 7, rank
    # Only LOCAL ROOTS put bytes on the cross-host allreduce leg —
    # a leaf's two-level legs (RAM) are deliberately not compressed,
    # so its counter holds EXACTLY the allgather's saving (tl.ag
    # ships a 16-byte f32 block at bf16 wire = 8 bytes saved;
    # allgather wire compression engages on every rank — it rides
    # the socket plane, which has no RAM leg).
    saved = _metric_value(hvd, "hvd_wire_bytes_saved_total")
    ag_saved = (2 * 2 * 4) // 2
    if hvd.local_rank() == 0:
        assert saved > ag_saved, rank
    else:
        assert saved == ag_saved, (rank, saved)


def scenario_compression_train_parity(hvd, rank, size):
    """Convergence-parity leg (ISSUE 9): train the toy TransformerLM
    from models/ data-parallel for a fixed schedule, gradients
    allreduced at this world's HOROVOD_COMPRESSION; rank 0 writes the
    loss trajectory for the pytest wrapper to compare across wire
    dtypes (none vs bf16 vs int8+error-feedback)."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss,
    )

    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            head_dim=8, max_seq_len=16,
                            dtype=jnp.float32)
    model = TransformerLM(cfg)
    data_rng = np.random.RandomState(4242 + rank)  # per-rank shards
    # FIXED batch per rank (memorization task): loss must fall
    # monotonically-ish within the short schedule, giving the parity
    # comparison a real training signal instead of noise-floor drift.
    tokens = jnp.asarray(data_rng.randint(0, 64, (4, 16)), jnp.int32)
    params = model.init(jax.random.key(0), tokens)  # identical ranks

    @jax.jit
    def loss_grads(p, t):
        def f(p):
            return lm_loss(model.apply(p, t), t)
        return jax.value_and_grad(f)(p)

    lr = 0.1
    losses = []
    for step in range(10):
        t = tokens
        loss, g = loss_grads(params, t)
        flat = [np.asarray(x, np.float32)
                for x in jax.tree_util.tree_leaves(g)]
        # SAME group name every step: the steady-state fast path (and
        # with it the compressed spec cycle) engages mid-run
        outs = hvd.grouped_allreduce(flat, average=True, name="gp")
        new_flat = [p - lr * jnp.asarray(gavg)
                    for p, gavg in zip(jax.tree_util.tree_leaves(params),
                                       outs)]
        params = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(params), new_flat)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), (rank, losses)
    # world-averaged final loss so every rank contributes to the
    # parity number the wrapper compares
    final = np.asarray(hvd.allreduce(
        np.asarray([losses[-1]], np.float64), average=True,
        name="gp.final"))
    out_path = os.environ.get("HVD_COMPRESSION_OUT")
    if rank == 0 and out_path:
        import json
        with open(out_path, "w") as f:
            json.dump({"final_loss": float(final[0]),
                       "losses": losses}, f)


def scenario_rank_death(hvd, rank, size):
    """A rank dying abruptly mid-job must surface on the survivors as
    a clean shutdown error on the next collective — never a hang
    (reference analog: shutdown fan-out + SHUT_DOWN_ERROR callbacks,
    operations.cc:898-913; under mpirun the dead orted kills the world,
    here the library itself detects the dead control channel)."""
    import time
    from horovod_tpu.common.status import HorovodInternalError
    x = np.full(50, float(rank + 1), np.float32)
    out = hvd.allreduce(x, average=False, name="rd.ok")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))
    if rank == 1:
        os._exit(0)  # abrupt death; 0 so the harness reads it as clean
    time.sleep(0.5)
    try:
        hvd.allreduce(x, average=False, name="rd.after")
        raise AssertionError("collective after a rank death must fail")
    except HorovodInternalError:
        pass
    # shutdown after the world collapsed stays idempotent
    hvd.shutdown()


def scenario_rank_death_hier(hvd, rank, size):
    """A REMOTE LEAF dying under the hierarchical control plane: its
    local root's relay recv fails, the root's background loop tears
    down, the coordinator sees that host's channel die, and every
    survivor errors out cleanly on its next collective — no hang at
    any tier of the hierarchy."""
    import time
    from horovod_tpu.common import basics as _b
    from horovod_tpu.common.status import HorovodInternalError

    topo = _b.runtime().controller.topology
    assert topo.cross_size > 1, "scenario expects a multihost topology"
    x = np.full(50, float(rank + 1), np.float32)
    out = hvd.allreduce(x, average=False, name="rdh.ok")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))
    if rank == size - 1:  # the last host's leaf (migrated behind root)
        os._exit(0)
    time.sleep(0.5)
    try:
        hvd.allreduce(x, average=False, name="rdh.after")
        raise AssertionError("collective after a leaf death must fail")
    except HorovodInternalError:
        pass
    hvd.shutdown()


def scenario_coordinator_death(hvd, rank, size):
    """The COORDINATOR (rank 0, which also hosts the controller socket)
    dying abruptly is the worst failure: every worker's control channel
    drops at once. Workers must fail loudly on their next collective and
    shut down cleanly — never hang (complements scenario_rank_death,
    which kills a non-coordinator)."""
    import time
    from horovod_tpu.common.status import HorovodInternalError
    x = np.full(16, float(rank + 1), np.float32)
    out = hvd.allreduce(x, average=False, name="cd.ok")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))
    if rank == 0:
        os._exit(0)  # coordinator vanishes, controller socket with it
    time.sleep(0.5)
    try:
        hvd.allreduce(x, average=False, name="cd.after")
        raise AssertionError(
            "collective after coordinator death must fail")
    except HorovodInternalError:
        pass
    hvd.shutdown()


def _await_world_abort(hvd, rank, expect_origin, deadline_s, name):
    """Drive allreduces until the fail-fast protocol surfaces
    :class:`WorldAbortedError`; assert it names the failed rank and
    lands within the detection deadline, then prove that a
    subsequently-enqueued handle fails the same structured way.

    No external watchdog does the unblocking here: if the in-band
    heartbeat/abort machinery regresses, the blocked collective trips
    the harness alarm guard and the test fails with thread stacks."""
    import time
    from horovod_tpu.common.status import WorldAbortedError

    t0 = time.monotonic()
    i = 0
    while True:
        try:
            hvd.allreduce(np.ones(64, np.float32), average=False,
                          name=f"{name}/{i}")
        except WorldAbortedError as e:
            elapsed = time.monotonic() - t0
            assert e.origin_rank == expect_origin, (
                f"rank {rank}: abort blamed rank {e.origin_rank}, "
                f"expected {expect_origin}: {e}")
            assert f"rank {expect_origin}" in str(e), str(e)
            assert elapsed < deadline_s, (
                f"rank {rank}: detection took {elapsed:.1f}s "
                f"(deadline {deadline_s}s)")
            break
        i += 1
        assert time.monotonic() - t0 < deadline_s, (
            f"rank {rank}: collectives kept succeeding for "
            f"{deadline_s}s after the fault")
    # handles enqueued AFTER the world died must fail structurally
    # too — never hang, never a bare UnknownError
    try:
        hvd.allreduce(np.ones(4, np.float32), average=False,
                      name=f"{name}/post")
        raise AssertionError("enqueue after world abort must fail")
    except WorldAbortedError as e:
        assert e.origin_rank == expect_origin, str(e)
    hvd.shutdown()  # stays idempotent after the world collapsed


def scenario_abort_sigkill_leaf(hvd, rank, size):
    """SIGKILL a non-coordinator rank squarely mid-allreduce (fault
    injection lands it just before that rank executes its 3rd
    negotiated response, while every peer is already inside the same
    collective): all survivors — including the coordinator — must
    raise WorldAbortedError naming the dead rank within the
    detection deadline. HOROVOD_FAULT_SPEC is set by the pytest
    wrapper (tests/test_multiprocess.py)."""
    victim = 1
    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    _await_world_abort(hvd, rank, victim, deadline, "sk.leaf")


def scenario_abort_sigkill_local_root(hvd, rank, size):
    """SIGKILL a LOCAL ROOT of the hierarchical control tier
    mid-collective: its leaves lose their upward relay, the
    coordinator loses that host's aggregate channel, and the abort
    must reach every survivor at every tier of the tree."""
    from horovod_tpu.common import basics as _b
    topo = _b.runtime().controller.topology
    assert topo.cross_size > 1, "scenario expects a multihost topology"
    victim = size // 2  # first rank of the second fake host = its root
    assert topo.local_roots[1] == victim
    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    _await_world_abort(hvd, rank, victim, deadline, "sk.root")


def scenario_abort_sigkill_coordinator(hvd, rank, size):
    """SIGKILL the coordinator (rank 0) mid-collective — the worst
    case: every worker's control channel dies at once, and there is no
    coordinator left to fan the ABORT. Workers must each detect the
    dead upward channel themselves and fail with WorldAbortedError
    naming rank 0."""
    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    _await_world_abort(hvd, rank, 0, deadline, "sk.coord")


def scenario_abort_heartbeat_hang(hvd, rank, size):
    """A rank that goes SILENT without dying (SIGSTOP-like wedge, host
    network loss: the kernel never sends FIN/RST, so TCP errors never
    fire) is detectable ONLY by the heartbeat recv deadline. Fault
    injection wedges rank 1's background loop; survivors must abort
    within HOROVOD_HEARTBEAT_TIMEOUT + slack, naming rank 1."""
    import time
    from horovod_tpu.common.status import HorovodInternalError

    victim = 1
    hb_timeout = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"])
    if rank == victim:
        # the wedged rank unblocks when its hang elapses, then finds
        # the world gone — any structured internal error is acceptable
        # on the faulty rank itself (it may blame the coordinator,
        # whose channel it finds dead on wake-up)
        try:
            while True:
                hvd.allreduce(np.ones(64, np.float32), average=False,
                              name="hb.hang")
        except HorovodInternalError:
            pass
        hvd.shutdown()
        return
    t0 = time.monotonic()
    _await_world_abort(hvd, rank, victim, hb_timeout + 15.0, "hb.hang")
    # the point of the heartbeat: detection is BOUNDED by the knob,
    # not by the wedge ending (6 s) or TCP keepalive (hours)
    assert time.monotonic() - t0 < hb_timeout + 15.0


def scenario_abort_sigkill_ring(hvd, rank, size):
    """SIGKILL a rank while the RING data plane is active (threshold
    lowered so these payloads ride the 2-phase ring): the survivor
    whose ring link dies must blame the dead NEIGHBOR, not itself,
    and the abort must fan to everyone."""
    victim = 1
    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    import time
    from horovod_tpu.common.status import WorldAbortedError

    t0 = time.monotonic()
    i = 0
    while True:
        try:
            # over HOROVOD_TPU_RING_THRESHOLD (1024) -> ring path
            hvd.allreduce(np.ones(50_000, np.float64), average=False,
                          name=f"rk/{i}")
        except WorldAbortedError as e:
            assert e.origin_rank == victim, (rank, e.origin_rank, str(e))
            assert time.monotonic() - t0 < deadline
            break
        i += 1
        assert time.monotonic() - t0 < deadline
    hvd.shutdown()


def scenario_abort_severed_link(hvd, rank, size):
    """Fault-injected link severance (abrupt close of rank 1's upward
    control channel, process still alive): both sides of the cut must
    converge on a world abort — the coordinator names the peer whose
    channel died; the severed rank finds its own channel closed."""
    from horovod_tpu.common.status import HorovodInternalError

    victim = 1
    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    if rank == victim:
        # After severing its own upward channel, this rank's next
        # control exchange fails; it blames its upward peer (rank 0)
        # since a cut wire is indistinguishable from a dead peer.
        try:
            while True:
                hvd.allreduce(np.ones(64, np.float32), average=False,
                              name="sever")
        except HorovodInternalError:
            pass
        hvd.shutdown()
        return
    _await_world_abort(hvd, rank, victim, deadline, "sever")


def scenario_subset_world(hvd, rank, size):
    """hvd.init(comm=[1, 2]) on a 3-process launch: ranks 1 and 2 form
    a 2-rank sub-world (renumbered 0 and 1, rank 1 hosting the
    coordinator) and allreduce; rank 0 is not a member, comes up as a
    size-1 world, and keeps working locally (reference:
    common/__init__.py:58-84 init(comm=ranks))."""
    assert size == 3, "scenario expects 3 launched processes"
    hvd.init(comm=[1, 2])
    if rank == 0:
        # the abstaining process: local world, local collectives work
        assert hvd.size() == 1 and hvd.rank() == 0
        out = hvd.allreduce(np.full(4, 7.0, np.float32),
                            average=False, name="solo.ar")
        np.testing.assert_allclose(out, 7.0)
    else:
        assert hvd.size() == 2
        assert hvd.rank() == rank - 1  # renumbered in list order
        x = np.full(5, float(rank), np.float32)  # global ranks 1, 2
        out = hvd.allreduce(x, average=False, name="sub.ar")
        np.testing.assert_allclose(out, 3.0)  # 1 + 2, never rank 0's 7
        b = hvd.broadcast(np.full(2, float(rank), np.float64),
                          root_rank=1, name="sub.bc")
        # sub-world root 1 == global rank 2
        np.testing.assert_allclose(b, 2.0)


scenario_subset_world.no_auto_init = True


def scenario_subset_world_hier(hvd, rank, size):
    """init(comm=[2..5]) on a 6-process launch with fake hosts
    rank//2: the sub-world spans two multi-rank hosts, so the
    HIERARCHICAL control plane activates INSIDE the subset — the
    sub-coordinator (global rank 2, renumbered 0) keeps one local leaf
    channel plus one aggregate channel for the remote host, and every
    collective stays exact; abstaining ranks keep local worlds."""
    assert size == 6, "scenario expects 6 launched processes"
    hvd.init(comm=[2, 3, 4, 5])
    from horovod_tpu.common import basics as _b

    if rank < 2:
        assert hvd.size() == 1
        out = hvd.allreduce(np.full(3, 5.0, np.float32),
                            average=False, name="solo.ar")
        np.testing.assert_allclose(out, 5.0)
        return
    assert hvd.size() == 4 and hvd.rank() == rank - 2
    ctl = _b.runtime().controller
    assert ctl.topology.cross_size == 2, ctl.topology.cross_size
    if hvd.rank() == 0:
        # 1 local leaf + 1 remote aggregate root
        assert len(ctl._channels) == 2, len(ctl._channels)
        assert ctl._has_aggregates

    x = np.full(5, float(rank), np.float32)  # global ranks 2..5
    out = hvd.allreduce(x, average=False, name="subh.ar")
    np.testing.assert_allclose(out, 14.0)  # 2+3+4+5, never ranks 0/1
    for root in range(4):
        b = hvd.broadcast(np.full(2, float(rank), np.float64),
                          root_rank=root, name=f"subh.bc{root}")
        np.testing.assert_allclose(b, float(root + 2))
    g = hvd.allgather(np.full((hvd.rank() + 1, 2), float(rank),
                              np.float32), name="subh.ag")
    off = 0
    for r in range(4):
        np.testing.assert_allclose(
            np.asarray(g)[off:off + r + 1], float(r + 2))
        off += r + 1


scenario_subset_world_hier.no_auto_init = True


def scenario_mxnet(hvd, rank, size):
    """Execute the whole MXNet adapter surface under a real 2-process
    world via the NDArray-protocol double (tests/fake_mxnet.py):
    collectives, in-place variants, parameter broadcast with deferred
    init, DistributedOptimizer (scalar + aggregated-list update), and
    DistributedTrainer._allreduce_grads (reference:
    horovod/mxnet/__init__.py:38-140)."""
    from tests import fake_mxnet
    fake_mxnet.install()
    import horovod_tpu.mxnet as hmx
    nd = fake_mxnet

    ssum = sum(range(1, size + 1))
    x = nd.NDArray(np.full(4, float(rank + 1), np.float32))
    out = hmx.allreduce(x, average=False, name="mx.ar")
    assert isinstance(out, nd.NDArray)
    np.testing.assert_allclose(out.asnumpy(), ssum)
    assert out.dtype == np.float32

    hmx.allreduce_(x, average=True, name="mx.ar_")
    np.testing.assert_allclose(x.asnumpy(), ssum / size)

    g = hmx.allgather(
        nd.NDArray(np.full((rank + 1, 2), float(rank), np.float32)),
        name="mx.ag")
    assert g.asnumpy().shape == (sum(r + 1 for r in range(size)), 2)

    b = hmx.broadcast(nd.NDArray(np.full(3, float(rank), np.float64)),
                      root_rank=1, name="mx.bc")
    np.testing.assert_allclose(b.asnumpy(), 1.0)

    # parameter broadcast with one deferred-init parameter: skipped on
    # the first pass, carried on the second after initialize()
    params = {
        "w": nd.Parameter("w", np.full(4, float(rank * 10 + 1))),
        "late": nd.Parameter("late", np.full(2, float(rank * 10 + 2)),
                             deferred=True),
    }
    hmx.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(params["w"].data().asnumpy(), 1.0)
    params["late"].initialize()
    hmx.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(params["late"].data().asnumpy(), 2.0)

    # DistributedOptimizer: scalar-index and aggregated-list updates
    class RecordingOpt:
        def __init__(self):
            self.calls = []

        def update(self, index, weight, grad, state):
            self.calls.append((index, grad))

        def update_multi_precision(self, index, weight, grad, state):
            self.calls.append(("mp", index, grad))

    opt = hmx.DistributedOptimizer(RecordingOpt())
    grad = nd.NDArray(np.full(3, float(rank + 1), np.float32))
    opt.update(7, None, grad, None)
    np.testing.assert_allclose(grad.asnumpy(), ssum / size)
    grads = [nd.NDArray(np.full(2, float(rank + 1) * (i + 1),
                                np.float32)) for i in range(2)]
    opt.update_multi_precision([1, 2], None, grads, None)
    for i, gr in enumerate(grads):
        np.testing.assert_allclose(gr.asnumpy(),
                                   ssum * (i + 1) / size)
    assert len(opt._opt.calls) == 2

    # DistributedTrainer: _allreduce_grads sums, _scale divides by size
    ps = [nd.Parameter(f"p{i}", np.ones(3),
                       grad=np.full(3, float(rank + 1) * (i + 1)))
          for i in range(2)]
    ps.append(nd.Parameter("frozen", np.ones(2),
                           grad=np.full(2, 99.0), grad_req="null"))
    trainer = hmx.DistributedTrainer(ps, RecordingOpt())
    assert trainer._scale == 1.0 / size
    trainer._allreduce_grads()
    for i in range(2):
        np.testing.assert_allclose(ps[i].list_grad()[0].asnumpy(),
                                   ssum * (i + 1))
    np.testing.assert_allclose(ps[2].list_grad()[0].asnumpy(), 99.0)

    # unwrap guard: a wrapped optimizer must not double-reduce
    t2 = hmx.DistributedTrainer(ps[:1], opt)
    assert not isinstance(t2._optimizer, hmx.DistributedOptimizer)


def scenario_autotune(hvd, rank, size):
    """End-to-end autotune under a real 2-process world: drive traffic
    until the coordinator's Bayesian tuner converges, then verify every
    worker adopted the coordinator's tuned values via the ResponseList
    trailer (reference: SyncParams, parameter_manager.cc:64-78)."""
    import time as _t
    from horovod_tpu.common import basics as _b
    rt = _b.runtime()
    pm = rt.parameter_manager
    assert pm is not None, "HOROVOD_AUTOTUNE=1 must create the manager"

    x = np.full(4096, float(rank + 1), np.float32)
    converged = False
    for i in range(2000):
        hvd.allreduce(x, average=False, name=f"at.{i}")
        # world-consistent loop exit: rank 0 broadcasts its tuning state
        flag = 0.0 if rank != 0 else (0.0 if pm.tuning else 1.0)
        done = hvd.broadcast(np.asarray([flag]), root_rank=0,
                             name=f"at.done/{i}")
        if float(done[0]) == 1.0:
            converged = True
            break
    assert converged, "autotune did not converge within the op budget"

    # one extra collective so the cycle that carried the converged
    # trailer has definitely passed through apply_synced on workers
    hvd.barrier(name="at.sync")
    _t.sleep(0.2)

    tuned = hvd.broadcast(np.asarray(pm._current, np.float64),
                          root_rank=0, name="at.vals")
    if rank != 0:
        # rtol bounded by the wire trailer's float32 round-trip
        np.testing.assert_allclose(np.asarray(pm._current, np.float64),
                                   tuned, rtol=1e-5)
        assert abs(pm.fusion_threshold_bytes()
                   - tuned[0] * 1024 * 1024) <= 1
        assert abs(pm.cycle_time_ms() - tuned[1]) < 1e-4


def scenario_shm_hier_allreduce(hvd, rank, size):
    """Multi-host (fake-host) world: allreduce rides the hierarchical
    shm path — local shm reduce, cross exchange among local roots,
    local shm broadcast (reference: NCCLHierarchicalAllreduce,
    nccl_operations.cc:167-372) — while other collectives stay on the
    socket backend."""
    from horovod_tpu.common import basics as _b
    ssum = sum(range(1, size + 1))

    x = np.arange(50_000, dtype=np.float64) + rank
    out = hvd.allreduce(x, average=False, name="sh.ar")
    np.testing.assert_allclose(
        out, size * np.arange(50_000, dtype=np.float64)
        + sum(range(size)))

    rt = _b.runtime()
    shm = [b for b in rt.op_manager._backends if b.name == "shm"][0]
    if hvd.local_size() > 1:
        assert shm._map is not None, "hier shm segment not established"
    else:
        # a solo host shares memory with nobody: no segment
        assert shm._map is None
    assert shm._hier, "topology should be multi-host"

    # zero-element allreduce must not wedge the protocol
    z = hvd.allreduce(np.empty(0, np.float32), average=False,
                      name="sh.zero")
    assert np.asarray(z).size == 0

    # fused batch + average through the hierarchical path
    handles = [hvd.allreduce_async(
        np.full(3000, float(rank + 1) * (i + 1), np.float32),
        average=True, name=f"sh.f/{i}") for i in range(4)]
    for i, h in enumerate(handles):
        np.testing.assert_allclose(
            hvd.synchronize(h), ssum * (i + 1) / size, rtol=1e-6)

    # segment growth in hier mode
    big = np.full(400_000, float(rank + 1), np.float32)
    np.testing.assert_allclose(
        hvd.allreduce(big, average=False, name="sh.big"), ssum)

    # non-allreduce collectives still work (socket backend path)
    g = hvd.allgather(np.full((rank + 1, 2), float(rank), np.float32),
                      name="sh.ag")
    assert g.shape[0] == sum(r + 1 for r in range(size))
    b = hvd.broadcast(np.full(3, float(rank), np.float64), root_rank=1,
                      name="sh.bc")
    np.testing.assert_allclose(b, 1.0)


def scenario_timeline(hvd, rank, size):
    """Drive one of each collective so rank 0's timeline (enabled via
    HOROVOD_TIMELINE in the harness env) records the full vocabulary
    (reference: test/test_timeline.py:42-58), including the fusion
    memcpy sub-activities a fused batch emits on the host planes
    (reference: mpi_operations.cc:35-62)."""
    x = np.full(64, float(rank + 1), np.float32)
    out = hvd.allreduce(x, average=False, name="tl.ar")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))
    g = hvd.allgather(np.full((rank + 1, 2), float(rank), np.float32),
                      name="tl.ag")
    assert g.shape[0] == sum(r + 1 for r in range(size))
    hvd.broadcast(x, root_rank=0, name="tl.bc")
    # grouped members are guaranteed one fused batch -> the pack/unpack
    # memcpy spans are emitted deterministically
    outs = hvd.grouped_allreduce(
        [np.full(16, float(rank + 1) * (i + 1), np.float32)
         for i in range(3)], average=False, name="tl.grp")
    for i, o in enumerate(outs):
        np.testing.assert_allclose(
            o, sum(range(1, size + 1)) * (i + 1.0))


def scenario_shm_fallback(hvd, rank, size):
    """Segment creation failing on one rank must degrade the whole
    world to the socket backend together (agree() vote)."""
    from horovod_tpu.common import basics as _b
    from horovod_tpu.ops import shm_ops as _shm

    if rank == 1:
        real_open = _shm.os.open

        def _fail(path, *a, **k):
            if isinstance(path, str) and path.startswith("/dev/shm/"):
                raise OSError("forced shm failure (test)")
            return real_open(path, *a, **k)
        _shm.os = type(_shm.os)("os_shim")
        _shm.os.__dict__.update(__import__("os").__dict__)
        _shm.os.open = _fail

    x = np.full(1000, float(rank + 1), np.float64)
    out = hvd.allreduce(x, average=False, name="sf.ar")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))

    rt = _b.runtime()
    shm = [b for b in rt.op_manager._backends if b.name == "shm"][0]
    assert shm._dead, "shm backend should be dead after the failed vote"
    assert shm._map is None

    # follow-up ops stay correct on the socket path
    out = hvd.allreduce(x, average=False, name="sf.ar2")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))


def scenario_shm_multihost_disabled(hvd, rank, size):
    from horovod_tpu.common import basics as _b
    x = np.full(100, float(rank + 1), np.float32)
    out = hvd.allreduce(x, average=False, name="mh.ar")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))
    rt = _b.runtime()
    shm = [b for b in rt.op_manager._backends if b.name == "shm"][0]
    assert shm._map is None, "shm must not establish across fake hosts"
    assert not shm.enabled([], None)


def scenario_barrier(hvd, rank, size):
    import time
    t0 = time.monotonic()
    if rank == 0:
        time.sleep(0.5)
    hvd.barrier(name="b1")
    if rank != 0:
        assert time.monotonic() - t0 >= 0.4, "barrier did not block"


def scenario_shape_mismatch_error(hvd, rank, size):
    # (reference: test_horovod_allreduce_error, test_tensorflow.py:265)
    from horovod_tpu.common.status import HorovodInternalError
    shape = (4, 5) if rank == 0 else (4, 6)
    try:
        hvd.allreduce(np.ones(shape, np.float32), name="bad_shape")
    except HorovodInternalError as e:
        assert "shape" in str(e).lower()
    else:
        raise AssertionError("expected HorovodInternalError")
    # world must still be usable after an ERROR response
    out = hvd.allreduce(np.ones(3, np.float32), average=False,
                        name="after_err")
    np.testing.assert_allclose(out, size * np.ones(3))


def scenario_dtype_mismatch_error(hvd, rank, size):
    # (reference: test_tensorflow.py:293)
    from horovod_tpu.common.status import HorovodInternalError
    dt = np.float32 if rank == 0 else np.float64
    try:
        hvd.allreduce(np.ones(4, dt), name="bad_dtype")
    except HorovodInternalError as e:
        assert "data type" in str(e).lower()
    else:
        raise AssertionError("expected HorovodInternalError")


def scenario_root_rank_mismatch_error(hvd, rank, size):
    # (reference: test_tensorflow.py:708)
    from horovod_tpu.common.status import HorovodInternalError
    try:
        hvd.broadcast(np.ones(4), root_rank=rank % size, name="bad_root")
    except HorovodInternalError as e:
        assert "root rank" in str(e).lower()
    else:
        raise AssertionError("expected HorovodInternalError")


def scenario_rank_subset_order(hvd, rank, size):
    """Out-of-order submission across ranks must still converge: rank 0
    submits a,b; rank 1 submits b,a — negotiation totals the order."""
    names = ["oo/a", "oo/b"] if rank == 0 else ["oo/b", "oo/a"]
    handles = {n: hvd.allreduce_async(np.full(5, float(rank), np.float32),
                                      average=False, name=n)
               for n in names}
    total = sum(range(size))
    for n, h in handles.items():
        np.testing.assert_allclose(hvd.synchronize(h),
                                   np.full(5, float(total)))


def scenario_hier_controller(hvd, rank, size):
    """Hierarchical control plane on a forced multihost topology
    (HOROVOD_HOSTNAME set by the harness): remote leaves must have
    migrated behind their host's local root, the coordinator must hold
    one channel per remote host, and every collective — hence every
    relayed control/data primitive, including broadcast from each kind
    of rank — must still be exact (control-plane analog of
    reference: horovod/common/operations.cc:729-764)."""
    from horovod_tpu.common import basics as _b

    rt = _b.runtime()
    ctl = rt.controller
    topo = ctl.topology
    assert topo.cross_size > 1, "scenario expects a multihost topology"
    if rank == 0:
        # Fan-in = host-0 leaves + one channel per remote host.
        expected_fanin = (topo.local_sizes[0] - 1) + (topo.cross_size - 1)
        assert len(ctl._channels) == expected_fanin, (
            len(ctl._channels), expected_fanin)
        assert ctl._has_aggregates, ctl._members
        agg = {o: ms for o, ms in ctl._members.items() if len(ms) > 1}
        assert agg, "no aggregate channels at the coordinator"
    elif topo.local_rank == 0:
        assert len(ctl._children) == topo.local_size - 1, ctl._children
    else:
        assert not ctl._children
        if topo.cross_rank != 0:
            # migrated: upward channel is the loopback root, not the
            # coordinator listener
            assert ctl._ch.sock.getpeername()[0] == "127.0.0.1"

    # allreduce incl. fusion through the aggregated gather
    handles = [hvd.allreduce_async(
        np.full(8, float(rank + 1) * (i + 1), np.float64),
        average=False, name=f"hc/ar{i}") for i in range(12)]
    ssum = sum(range(1, size + 1))
    for i, h in enumerate(handles):
        np.testing.assert_allclose(
            hvd.synchronize(h), np.full(8, ssum * (i + 1), np.float64))

    # variable-dim0 allgather (exercises per-rank sizes surviving the
    # aggregate frame unpack in rank order)
    out = hvd.allgather(np.full((rank + 1, 2), float(rank), np.float32),
                        name="hc/ag")
    off = 0
    for r in range(size):
        np.testing.assert_allclose(out[off:off + r + 1],
                                   np.full((r + 1, 2), float(r)))
        off += r + 1

    # broadcast from EVERY root: coordinator, host-0 leaf, remote
    # root, remote leaf — each takes a different relay branch
    for root in range(size):
        x = np.full((5,), float(rank * 10), np.float64)
        outb = hvd.broadcast(x, root_rank=root, name=f"hc/bc{root}")
        np.testing.assert_allclose(outb, np.full((5,), float(root * 10)))

    # alltoall + reducescatter + barrier over the relayed data plane
    per = 2
    x = np.arange(size * per, dtype=np.float32) + 100 * rank
    outa = hvd.alltoall(x, name="hc/a2a")
    expected = np.concatenate(
        [np.arange(rank * per, (rank + 1) * per) + 100 * src
         for src in range(size)]).astype(np.float32)
    np.testing.assert_allclose(outa, expected)

    x = np.arange(size * 3, dtype=np.float32) * (rank + 1)
    outr = hvd.reducescatter(x, name="hc/rs")
    np.testing.assert_allclose(
        outr, np.arange(rank * 3, (rank + 1) * 3) * ssum)

    hvd.barrier(name="hc/bar")


def scenario_flat_controller_multihost(hvd, rank, size):
    """With HOROVOD_TPU_HIER_CONTROLLER=0 a multihost topology keeps
    the flat star: every worker stays directly connected to the
    coordinator and no aggregate channels exist."""
    from horovod_tpu.common import basics as _b

    ctl = _b.runtime().controller
    assert ctl.topology.cross_size > 1
    if rank == 0:
        assert len(ctl._channels) == size - 1, len(ctl._channels)
        assert not ctl._has_aggregates
    else:
        assert not ctl._children
    out = hvd.allreduce(np.full(6, float(rank + 1), np.float32),
                        average=False, name="flat/ar")
    np.testing.assert_allclose(
        out, np.full(6, sum(range(1, size + 1)), np.float32))
    hvd.barrier(name="flat/bar")


def scenario_topology(hvd, rank, size):
    assert hvd.rank() == rank
    assert hvd.size() == size
    # all ranks in these tests run on one host
    assert hvd.local_size() == size
    assert hvd.local_rank() == rank
    assert hvd.cross_size() == 1
    assert hvd.is_homogeneous()


def scenario_stall_shutdown(hvd, rank, size):
    """Rank 1 never submits; stall inspector must shut the job down
    (reference analog: test/test_stall.py)."""
    from horovod_tpu.common.status import HorovodInternalError
    if rank == 0:
        try:
            hvd.allreduce(np.ones(4, np.float32), name="stalled")
        except HorovodInternalError:
            return
        raise AssertionError("expected stall shutdown error")
    else:
        import time
        time.sleep(5.0)




def scenario_torch_optimizer(hvd_mod, rank, size):
    """torch adapter end-to-end: broadcast params, hook-driven async
    grad allreduce, optimizer-state broadcast (reference analog:
    test_torch.py:802-1003 + the DistributedOptimizer flow)."""
    import torch
    import horovod_tpu.torch as hvd

    torch.manual_seed(100 + rank)  # rank-divergent init on purpose
    model = torch.nn.Sequential(
        torch.nn.Linear(6, 4), torch.nn.ReLU(), torch.nn.Linear(4, 2))
    opt = torch.optim.SGD(model.parameters(), lr=0.05, momentum=0.9,
                          weight_decay=1e-4)

    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    # after broadcast all ranks agree parameter-wise
    flat = torch.cat([p.detach().reshape(-1) for p in model.parameters()])
    gathered = hvd.allgather(flat.reshape(1, -1), name="check.init")
    for r in range(size):
        assert torch.allclose(gathered[r], gathered[0]), "params diverged"

    dopt = hvd.DistributedOptimizer(
        opt, named_parameters=model.named_parameters())
    torch.manual_seed(1234 + rank)
    for step in range(3):
        x = torch.randn(8, 6)
        y = torch.randn(8, 2)
        dopt.zero_grad()
        loss = torch.nn.functional.mse_loss(model(x), y)
        loss.backward()
        dopt.step()
    flat = torch.cat([p.detach().reshape(-1) for p in model.parameters()])
    gathered = hvd.allgather(flat.reshape(1, -1), name="check.final")
    for r in range(size):
        assert torch.allclose(gathered[r], gathered[0], atol=1e-6), \
            "rank-divergent data should still yield identical params"

    hvd.broadcast_optimizer_state(opt, root_rank=0)
    g = opt.param_groups[0]
    assert g["lr"] == 0.05 and g["momentum"] == 0.9
    assert abs(g["weight_decay"] - 1e-4) < 1e-12
    assert isinstance(g.get("nesterov", False), bool)


def scenario_torch_allreduce_grad(hvd_mod, rank, size):
    """Gradient flows THROUGH hvd.allreduce (reference:
    test_horovod_allreduce_grad, test_torch.py:377): the backward of a
    sum-allreduce sums the upstream gradients, average averages them."""
    import torch
    import horovod_tpu.torch as hvd

    x = torch.full((5,), float(rank + 1), requires_grad=True)
    y = hvd.allreduce(x, op=hvd.Sum, name="g.sum")
    assert torch.allclose(y, torch.full((5,),
                                        float(sum(range(1, size + 1)))))
    y.sum().backward()
    # upstream ones, sum-allreduced across ranks -> size
    assert torch.allclose(x.grad, torch.full((5,), float(size))), x.grad

    x2 = torch.full((3,), float(rank + 1), requires_grad=True)
    hvd.allreduce(x2, op=hvd.Average, name="g.avg").sum().backward()
    # upstream ones, averaged -> ones
    assert torch.allclose(x2.grad, torch.ones(3)), x2.grad

    # no-grad tensors keep the plain (non-autograd) path
    z = torch.full((4,), float(rank + 1))
    out = hvd.allreduce(z, op=hvd.Sum, name="g.nograd")
    assert not out.requires_grad

    # double backward (gradient-penalty style): when the upstream
    # gradient itself carries a graph (nonlinear loss), the backward
    # recursion must keep it differentiable instead of silently
    # cutting the second order at the collective
    ssum = sum(range(1, size + 1))
    x3 = torch.full((2,), float(rank + 1), requires_grad=True)
    y3 = hvd.allreduce(x3, op=hvd.Sum, name="g.dd")
    loss = (y3 ** 2).sum()
    (g,) = torch.autograd.grad(loss, x3, create_graph=True)
    # g = sum-allreduce(2*y3) = 2 * size * ssum  (y3 == ssum everywhere)
    assert torch.allclose(g, torch.full((2,), 2.0 * size * ssum)), g
    assert g.requires_grad, "create_graph lost through the collective"
    (g2,) = torch.autograd.grad(g.sum(), x3)
    # two nested sum-allreduces of ones: 2 * size * size
    assert torch.allclose(g2, torch.full((2,), 2.0 * size * size)), g2


def scenario_torch_adam_state(hvd_mod, rank, size):
    """broadcast_optimizer_state with tuple hyperparameters (Adam's
    betas) and materialized per-param state incl. int step counters —
    tuples must be rebuilt, not assigned into (reference analog:
    test_torch.py:802-1003 covering every optimizer class)."""
    import torch
    import horovod_tpu.torch as hvd

    torch.manual_seed(200 + rank)
    model = torch.nn.Linear(5, 3)
    # rank-divergent hyperparams: the broadcast must impose rank 0's
    betas = (0.9, 0.999) if rank == 0 else (0.5, 0.7)
    lr = 1e-3 if rank == 0 else 0.1
    opt = torch.optim.Adam(model.parameters(), lr=lr, betas=betas,
                           amsgrad=False)
    # materialize state (exp_avg tensors + int step counters)
    loss = model(torch.randn(4, 5)).sum()
    loss.backward()
    opt.step()

    hvd.broadcast_optimizer_state(opt, root_rank=0)
    g = opt.param_groups[0]
    assert isinstance(g["betas"], tuple), type(g["betas"])
    assert g["betas"] == (0.9, 0.999), g["betas"]
    assert abs(g["lr"] - 1e-3) < 1e-12, g["lr"]
    # tensor state agrees world-wide after broadcast
    for pid, st in opt.state_dict()["state"].items():
        for key, val in st.items():
            if isinstance(val, torch.Tensor):
                gathered = hvd.allgather(
                    val.detach().reshape(1, -1).to(torch.float32),
                    name=f"check.adam.{pid}.{key}")
                for r in range(size):
                    assert torch.allclose(gathered[r], gathered[0]), \
                        f"state {pid}/{key} diverged"


def scenario_torch_opt_state_asymmetric(hvd_mod, rank, size):
    """The checkpoint-restore shape broadcast_optimizer_state exists
    for: ONLY rank 0 has materialized state (it "loaded a checkpoint");
    workers hold fresh optimizers. Without empty-state materialization
    (reference: horovod/torch/__init__.py:249-271) rank 0 submits
    broadcasts the workers never submit and the world hangs."""
    import torch
    import horovod_tpu.torch as hvd

    torch.manual_seed(300 + rank)
    model = torch.nn.Linear(4, 2)
    # A frozen parameter: real training on rank 0 never gives it a
    # gradient, so rank 0's state has NO entry for it. Worker-side
    # materialization must also skip it or the broadcast structures
    # disagree and the world hangs.
    model.bias.requires_grad_(False)
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    if rank == 0:
        # rank 0 materializes real (non-zero) state
        loss = model(torch.randn(3, 4)).sum()
        loss.backward()
        opt.step()
        opt.zero_grad()
    assert bool(opt.state_dict()["state"]) == (rank == 0)

    hvd.broadcast_optimizer_state(opt, root_rank=0)

    st = opt.state_dict()["state"]
    assert st, "workers must have materialized state after broadcast"
    for pid, entry in st.items():
        for key, val in entry.items():
            if isinstance(val, torch.Tensor) and val.numel():
                gathered = hvd.allgather(
                    val.detach().reshape(1, -1).to(torch.float32),
                    name=f"check.asym.{pid}.{key}")
                for r in range(size):
                    assert torch.allclose(gathered[r], gathered[0]), \
                        f"state {pid}/{key} diverged after restore bcast"

    # Stateless optimizer: every rank takes the early return, no wire
    # traffic, no hang (reference :266-271).
    sgd = torch.optim.SGD(model.parameters(), lr=0.1)
    hvd.broadcast_optimizer_state(sgd, root_rank=0)
    assert not sgd.state_dict()["state"]

    # LBFGS is rejected up front on every rank (reference :241-245),
    # including when hidden behind the DistributedOptimizer wrapper.
    lbfgs = torch.optim.LBFGS([p for p in model.parameters()
                               if p.requires_grad])
    for candidate in (lbfgs, hvd.DistributedOptimizer(lbfgs)):
        try:
            hvd.broadcast_optimizer_state(candidate, root_rank=0)
        except ValueError:
            pass
        else:
            raise AssertionError("LBFGS broadcast must raise ValueError")

    # world still healthy after the error path
    one = hvd.allreduce(torch.ones(2), name="asym.final", op=hvd.Sum)
    assert torch.allclose(one, torch.full((2,), float(size)))


def scenario_jax_adapter(hvd_mod, rank, size):
    """jax adapter host path: pytree gradient allreduce + parameter
    broadcast through the background runtime."""
    import horovod_tpu.jax as hvd

    grads = {"w": np.full((3, 2), float(rank + 1), np.float32),
             "b": np.full((2,), float(rank + 1), np.float32)}
    out = hvd.allreduce_gradients(grads, op=hvd.Average)
    mean = sum(range(1, size + 1)) / size
    np.testing.assert_allclose(out["w"], mean)
    np.testing.assert_allclose(out["b"], mean)

    params = {"w": np.full((4,), float(rank), np.float32)}
    out = hvd.broadcast_parameters(params, root_rank=1)
    np.testing.assert_allclose(out["w"], 1.0)

    comp = hvd.allreduce_gradients(
        {"g": np.full((8,), float(rank + 1), np.float32)},
        op=hvd.Average, compression=hvd.Compression.fp16)
    np.testing.assert_allclose(comp["g"], mean, rtol=1e-3)



def scenario_tf_sparse_as_dense(hvd_mod, rank, size):
    """sparse_as_dense=True must produce the same effective gradient
    as the IndexedSlices gather path, bit-for-bit on exactly
    representable values (reference:
    horovod/tensorflow/__init__.py:157,195-202). Uses overlapping AND
    duplicated indices so scatter-add summing is actually exercised."""
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvd_tf

    # rank r touches rows {r, r+1} of a 4-row embedding, with row
    # r+1 duplicated — integer-valued floats keep both paths exact
    values = tf.constant(np.array(
        [[2.0 * (rank + 1)] * 3,
         [4.0 * (rank + 1)] * 3,
         [6.0 * (rank + 1)] * 3], np.float32))
    indices = tf.constant(np.array([rank, rank + 1, rank + 1], np.int64))
    dense_shape = tf.constant([size + 1, 3], tf.int64)

    def _make():
        return tf.IndexedSlices(values, indices, dense_shape=dense_shape)

    # gather path -> IndexedSlices; densify to compare
    sparse_out = hvd_tf.allreduce(_make(), op=hvd_tf.Average,
                                  name="sad.gather")
    assert isinstance(sparse_out, tf.IndexedSlices)
    via_gather = tf.scatter_nd(
        tf.expand_dims(sparse_out.indices, 1), sparse_out.values,
        dense_shape).numpy()

    # dense path -> plain tensor
    dense_out = hvd_tf.allreduce(_make(), op=hvd_tf.Average,
                                 name="sad.dense", sparse_as_dense=True)
    assert not isinstance(dense_out, tf.IndexedSlices)
    assert dense_out.shape == (size + 1, 3)

    np.testing.assert_array_equal(dense_out.numpy(), via_gather)

    # and through DistributedOptimizer(sparse_as_dense=True): the
    # applied update must equal the gather-path update exactly
    var = tf.Variable(np.zeros((size + 1, 3), np.float32))
    opt = hvd_tf.DistributedOptimizer(
        tf.keras.optimizers.SGD(1.0), sparse_as_dense=True)
    opt.apply_gradients([(_make(), var)])
    np.testing.assert_array_equal(var.numpy(), -via_gather)


def scenario_tf_broadcast_hook(hvd_mod, rank, size):
    """BroadcastGlobalVariablesHook must be a REAL SessionRunHook that
    broadcasts rank 0's variables through a TF1 MonitoredTrainingSession
    (reference: horovod/tensorflow/__init__.py:117-148)."""
    import tensorflow as tf
    tf.compat.v1.disable_eager_execution()
    import horovod_tpu.tensorflow as hvd_tf

    v = tf.compat.v1.get_variable(
        "v", initializer=np.full((3, 2), float(rank + 7), np.float32))
    hook = hvd_tf.BroadcastGlobalVariablesHook(0)
    assert isinstance(hook, tf.compat.v1.train.SessionRunHook), type(hook)
    with tf.compat.v1.train.MonitoredTrainingSession(
            hooks=[hook]) as sess:
        out = sess.run(v)
    np.testing.assert_allclose(out, np.full((3, 2), 7.0))


def scenario_keras_optimizer(hvd_mod, rank, size):
    """keras DistributedOptimizer: rank-divergent data, identical
    weights after fit (reference analog: test_keras.py:62-186 +
    test_tensorflow_keras.py:46 test_train_model)."""
    import os
    os.environ.setdefault("KERAS_BACKEND", "tensorflow")
    import keras
    import horovod_tpu.keras as hvd

    keras.utils.set_random_seed(42)  # same init everywhere
    model = keras.Sequential([
        keras.layers.Input((4,)),
        keras.layers.Dense(3, activation="relu"),
        keras.layers.Dense(2),
    ])
    opt = hvd.DistributedOptimizer(keras.optimizers.SGD(0.05))
    model.compile(optimizer=opt, loss="mse")
    rng = np.random.RandomState(rank)  # different data per rank
    x = rng.randn(16, 4).astype(np.float32)
    y = rng.randn(16, 2).astype(np.float32)
    model.fit(x, y, epochs=1, batch_size=8, verbose=0)

    flat = np.concatenate([w.reshape(-1) for w in model.get_weights()])
    gathered = hvd_mod.allgather(flat.reshape(1, -1), name="keras.check")
    for r in range(size):
        np.testing.assert_allclose(gathered[r], gathered[0], atol=1e-6)


def scenario_tfkeras_facade(hvd_mod, rank, size):
    """horovod_tpu.tensorflow.keras (the tf.keras facade, reference:
    horovod/tensorflow/keras/__init__.py): DistributedOptimizer +
    BroadcastGlobalVariablesCallback through model.fit, then a
    save -> load_model round trip that re-wraps the optimizer."""
    import os
    import tempfile
    os.environ.setdefault("KERAS_BACKEND", "tensorflow")
    import tensorflow as tf
    import horovod_tpu.tensorflow.keras as hvd

    tf.keras.utils.set_random_seed(100 + rank)  # divergent init
    model = tf.keras.Sequential([
        tf.keras.layers.Input((4,)),
        tf.keras.layers.Dense(3, activation="relu"),
        tf.keras.layers.Dense(2),
    ])
    opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.05))
    model.compile(optimizer=opt, loss="mse")
    rng = np.random.RandomState(rank)
    x = rng.randn(16, 4).astype(np.float32)
    y = rng.randn(16, 2).astype(np.float32)
    # the broadcast callback must erase the divergent initialization
    model.fit(x, y, epochs=1, batch_size=8, verbose=0, callbacks=[
        hvd.callbacks.BroadcastGlobalVariablesCallback(0)])

    flat = np.concatenate([w.reshape(-1) for w in model.get_weights()])
    gathered = hvd_mod.allgather(flat.reshape(1, -1), name="tfk.check")
    for r in range(size):
        np.testing.assert_allclose(gathered[r], gathered[0], atol=1e-6)

    # save/load round trip restores a DISTRIBUTED optimizer; a plain
    # keras load of the same file must fail loudly (the reference's
    # failure mode, never a silently-undistributed optimizer)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "model.keras")
        model.save(path)
        loaded = hvd.load_model(path)
        assert getattr(loaded.optimizer, "_hvd_wrapped", False)
        try:
            tf.keras.models.load_model(path)
            raise AssertionError("plain load should fail loudly")
        except TypeError:
            pass

    # reference call shape broadcast_global_variables(root) fails with
    # guidance, not a confusing attribute error
    try:
        hvd.broadcast_global_variables(0)
        raise AssertionError("old call shape should raise TypeError")
    except TypeError as e:
        assert "BroadcastGlobalVariablesCallback" in str(e)


def scenario_tf_tape(hvd_mod, rank, size):
    """DistributedGradientTape averages grads across ranks
    (reference analog: test_tensorflow.py:334 allreduce_grad)."""
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvd

    v = tf.Variable([1.0, 2.0, 3.0])
    with hvd.DistributedGradientTape(tf.GradientTape()) as tape:
        loss = tf.reduce_sum(v * float(rank + 1))
    grads = tape.gradient(loss, [v])
    mean = sum(range(1, size + 1)) / size
    np.testing.assert_allclose(grads[0].numpy(), [mean] * 3, rtol=1e-6)

    bcast = tf.Variable([float(rank)] * 4)
    hvd.broadcast_variables([bcast], root_rank=1)
    np.testing.assert_allclose(bcast.numpy(), [1.0] * 4)


def scenario_tf_allreduce_grad(hvd_mod, rank, size):
    """Gradient flows through the standalone TF allreduce under
    GradientTape (reference: the registered HorovodAllreduce gradient,
    tensorflow/mpi_ops.py)."""
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvd

    x = tf.constant([float(rank + 1)] * 4)
    with tf.GradientTape() as tape:
        tape.watch(x)
        y = hvd.allreduce(x, op=hvd.Sum, name="tg.ar")
        loss = tf.reduce_sum(y)
    assert np.allclose(y.numpy(), sum(range(1, size + 1)))
    g = tape.gradient(loss, x)
    # upstream ones, sum-allreduced -> size
    assert np.allclose(g.numpy(), float(size)), g.numpy()

    # average semantics in the gradient too
    x2 = tf.constant([float(rank + 1)] * 3)
    with tf.GradientTape() as tape:
        tape.watch(x2)
        loss = tf.reduce_sum(hvd.allreduce(x2, op=hvd.Average,
                                           name="tg.avg"))
    assert np.allclose(tape.gradient(loss, x2).numpy(), 1.0)

    # variables differentiate exactly like tensors
    v = tf.Variable([float(rank + 1)] * 2)
    with tf.GradientTape() as tape:
        loss = tf.reduce_sum(hvd.allreduce(v, op=hvd.Sum,
                                           name="tg.var"))
    assert np.allclose(tape.gradient(loss, v).numpy(),
                       float(size)), "variable gradient lost"

    # python scalars still work on the plain path
    s = hvd.allreduce(3.0 * (rank + 1), op=hvd.Sum, name="tg.scalar")
    assert np.allclose(np.asarray(s), 3.0 * sum(range(1, size + 1)))


def scenario_torch_gather_bcast_grad(hvd_mod, rank, size):
    """Gradients flow through torch allgather (variable dim-0) and
    broadcast (reference: HorovodAllgather / HorovodBroadcast autograd
    Functions, horovod/torch/mpi_ops.py:236-334)."""
    import torch
    import horovod_tpu.torch as hvd

    # -- allgather: rank r contributes r+1 rows of 2 ---------------------
    d0 = rank + 1
    x = torch.full((d0, 2), float(rank + 1), requires_grad=True)
    total_rows = sum(r + 1 for r in range(size))
    w = torch.arange(total_rows, dtype=torch.float32)[:, None] + 1.0
    y = hvd.allgather(x, name="tg.ag")
    assert y.shape == (total_rows, 2)
    (y * w).sum().backward()
    off = sum(r + 1 for r in range(rank))
    want = size * (np.arange(total_rows, dtype=np.float32)[:, None]
                   + 1.0)[off:off + d0]
    np.testing.assert_allclose(x.grad.numpy(),
                               np.broadcast_to(want, (d0, 2)))

    # -- broadcast: non-root inputs get exact zero gradient --------------
    root = size - 1
    v = torch.full((3,), float(rank + 10), requires_grad=True)
    yb = hvd.broadcast(v, root_rank=root, name="tg.bc")
    np.testing.assert_allclose(yb.detach().numpy(), float(root + 10))
    (yb * float(rank + 1)).sum().backward()
    ssum = sum(range(1, size + 1))
    if rank == root:
        np.testing.assert_allclose(v.grad.numpy(), float(ssum))
    else:
        np.testing.assert_allclose(v.grad.numpy(), 0.0)

    # broadcast_ stays in-place and non-differentiable, even on a
    # requires_grad leaf (the reference contract)
    p = torch.full((2,), float(rank), requires_grad=True)
    out = hvd.broadcast_(p, root_rank=0, name="tg.bc_")
    assert out is p and p.grad_fn is None
    np.testing.assert_allclose(p.detach().numpy(), 0.0)


def scenario_tf_gather_bcast_grad(hvd_mod, rank, size):
    """Gradients flow through TF allgather (variable dim-0!) and
    broadcast (reference: the registered HorovodAllgather /
    HorovodBroadcast gradients, tensorflow/mpi_ops.py:127-181):
    allgather's grad is this rank's slice of the sum-allreduced
    upstream; broadcast's grad is the summed upstream on the root and
    zeros elsewhere."""
    import tensorflow as tf
    import horovod_tpu.tensorflow as hvd

    # -- allgather: rank r contributes r+1 rows of 2 ---------------------
    d0 = rank + 1
    x = tf.constant(np.full((d0, 2), float(rank + 1), np.float32))
    # per-GLOBAL-row weights, identical on every rank
    total_rows = sum(r + 1 for r in range(size))
    w = tf.constant(np.arange(total_rows,
                              dtype=np.float32)[:, None] + 1.0)
    with tf.GradientTape() as tape:
        tape.watch(x)
        y = hvd.allgather(x, name="tg.ag")
        loss = tf.reduce_sum(y * w)
    assert y.shape == (total_rows, 2)
    g = tape.gradient(loss, x)
    # upstream dL/dy = w on every rank; sum over ranks = size * w;
    # our slice starts at sum of earlier ranks' sizes
    off = sum(r + 1 for r in range(rank))
    want = size * (np.arange(total_rows, dtype=np.float32)[:, None]
                   + 1.0)[off:off + d0]
    assert np.allclose(g.numpy(), want), (g.numpy(), want)

    # -- broadcast: non-root inputs get zero gradient --------------------
    root = size - 1
    v = tf.Variable(np.full(3, float(rank + 10), np.float32))
    with tf.GradientTape() as tape:
        y = hvd.broadcast(v, root_rank=root, name="tg.bc")
        loss = tf.reduce_sum(y * float(rank + 1))
    assert np.allclose(y.numpy(), float(root + 10))
    g = tape.gradient(loss, v)
    ssum = sum(range(1, size + 1))
    if rank == root:
        assert np.allclose(g.numpy(), float(ssum)), g.numpy()
    else:
        assert np.allclose(g.numpy(), 0.0), g.numpy()


def scenario_scalar_broadcast(hvd_mod, rank, size):
    """0-d tensors must round-trip broadcast with shape intact
    (regression: ascontiguousarray promotes 0-d to (1,))."""
    out = hvd_mod.broadcast(np.asarray(float(rank)), root_rank=1,
                            name="scalar")
    assert np.asarray(out).shape == (), np.asarray(out).shape
    assert float(np.asarray(out)) == 1.0


def scenario_checkpoint_resume(hvd_mod, rank, size):
    """rank-0 save + broadcast restore: every rank ends bit-identical
    (reference resume contract: rank-0 checkpoint + state broadcast,
    SURVEY section 5)."""
    import tempfile, os
    from horovod_tpu.utils import save_checkpoint, restore_checkpoint

    tmp = os.environ["HVD_TEST_CKPT_DIR"]
    state = {"w": np.full((4,), 7.5, np.float32) if rank == 0
             else np.zeros((4,), np.float32),
             "step": np.asarray(3, np.int64) if rank == 0
             else np.asarray(0, np.int64)}
    save_checkpoint(tmp, state, step=3)
    hvd_mod.barrier(name="after-save")
    target = {"w": np.zeros((4,), np.float32),
              "step": np.asarray(0, np.int64)}
    restored = restore_checkpoint(tmp, target=target, broadcast=True)
    np.testing.assert_allclose(np.asarray(restored["w"]), 7.5)
    assert int(np.asarray(restored["step"])) == 3


def _init_jax_distributed(rank, size):
    import os
    import jax
    jax.config.update("jax_platforms", "cpu")
    port = int(os.environ["HOROVOD_CONTROLLER_PORT"]) + 1000
    jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=size,
                               process_id=rank)
    return jax


def scenario_xla_backend(hvd_mod, rank, size):
    """Collectives on jax arrays in a REAL multi-process JAX world:
    the XlaMeshBackend path (negotiation -> fused psum over the proc
    mesh), not the socket fallback."""
    jax = _init_jax_distributed(rank, size)
    import jax.numpy as jnp

    x = jnp.full((8,), float(rank + 1), jnp.float32)
    out = hvd_mod.allreduce(x, average=False, name="xla.ar")
    ssum = sum(range(1, size + 1))
    assert hasattr(out, "devices"), "output should stay a jax array"
    np.testing.assert_allclose(np.asarray(out), ssum)

    # fused batch (several tensors in one cycle -> one compiled psum)
    handles = [hvd_mod.allreduce_async(
        jnp.full((4,), float(rank + 1) * (i + 1), jnp.float32),
        average=False, name=f"xla.f/{i}") for i in range(8)]
    for i, h in enumerate(handles):
        np.testing.assert_allclose(
            np.asarray(hvd_mod.synchronize(h)), ssum * (i + 1),
            rtol=1e-6)

    # broadcast with non-zero root (one-to-all collective-permute
    # path) — every root must deliver its own values
    for root in range(size):
        b = jnp.full((3,), float(rank * 10), jnp.float32)
        out = hvd_mod.broadcast(b, root_rank=root,
                                name=f"xla.bc/{root}")
        np.testing.assert_allclose(np.asarray(out), float(root * 10))
    # 0-d scalar broadcast rides the same path
    s = hvd_mod.broadcast(jnp.asarray(float(rank + 7)), root_rank=1,
                          name="xla.bc0d")
    np.testing.assert_allclose(np.asarray(s), 8.0)

    g = hvd_mod.allgather(
        jnp.full((rank + 1, 2), float(rank), jnp.float32), name="xla.ag")
    assert np.asarray(g).shape == (sum(range(1, size + 1)) + 0, 2) or         np.asarray(g).shape[0] == sum(r + 1 for r in range(size))

    # fused multi-entry allgather on the mesh: several variable-dim0
    # gathers submitted together execute as one padded all_gather +
    # per-entry slice (multi-entry execute_allgather)
    seen = _record_batches(hvd_mod)
    hs = [hvd_mod.allgather_async(
        jnp.full((rank + 1 + (i % 2), i + 1), float(rank * 10 + i),
                 jnp.float32), name=f"xla.fag.{i}") for i in range(6)]
    for i, h in enumerate(hs):
        out = np.asarray(hvd_mod.synchronize(h))
        total_rows = sum(r + 1 + (i % 2) for r in range(size))
        assert out.shape == (total_rows, i + 1), (i, out.shape)
        off = 0
        for r in range(size):
            rr = r + 1 + (i % 2)
            np.testing.assert_allclose(
                out[off:off + rr],
                np.full((rr, i + 1), float(r * 10 + i)))
            off += rr
    ag_batches = [names for kind, names in seen if kind == "ALLGATHER"]
    assert any(len(b) >= 2 for b in ag_batches), \
        f"no fused xla allgather batch: {ag_batches}"

    # empty entries inside the mesh path: one some-ranks-empty entry
    # (rank 0 contributes 0 rows) next to a normal one
    h1 = hvd_mod.allgather_async(
        jnp.full((rank, 2), float(rank), jnp.float32), name="xla.e.some")
    h2 = hvd_mod.allgather_async(
        jnp.full((2, 2), float(rank + 5), jnp.float32), name="xla.e.full")
    out = np.asarray(hvd_mod.synchronize(h1))
    assert out.shape == (sum(range(size)), 2), out.shape
    off = 0
    for r in range(size):
        np.testing.assert_allclose(out[off:off + r], float(r))
        off += r
    out = np.asarray(hvd_mod.synchronize(h2))
    for r in range(size):
        np.testing.assert_allclose(out[2 * r:2 * r + 2], float(r + 5))


def scenario_xla_async_overlap(hvd_mod, rank, size):
    """END-TO-END negotiation/execution overlap on the real XLA plane:
    a deliberately slow big collective (completion-observation delayed
    2.5 s) must not stop later cycles from negotiating, issuing, and
    COMPLETING smaller collectives through the real TCP gather — and
    rank 0's timeline must show the smalls' NEGOTIATE spans inside the
    big one's COLLECTIVE span (reference purpose: FinalizeCUDAQueue,
    cuda_operations.cc:148-179)."""
    import time as _t

    jax = _init_jax_distributed(rank, size)
    import jax.numpy as jnp
    from horovod_tpu.common import basics as _b

    # Warm the compiled paths AND measure this host's real round-trip
    # cost, so every timing bound below scales with the machine
    # instead of hard-coding wall-clock races.
    t0 = _t.monotonic()
    for i in range(3):
        hvd_mod.allreduce(jnp.full((4,), 1.0, jnp.float32),
                          average=False, name=f"ov.warm.{i}")
    rtt = max(0.05, (_t.monotonic() - t0) / 3)
    issue_wait = max(0.3, 3 * rtt)
    delay = max(2.5, 20 * rtt)

    rt = _b.runtime()
    xla = [b for b in rt.op_manager._backends if b.name == "xla_mesh"][0]
    orig_observe = xla._observe
    BIG = 1 << 16

    def slow_observe(outs):
        if any(getattr(o, "size", 0) >= BIG for o in outs):
            _t.sleep(delay)
        return orig_observe(outs)

    xla._observe = slow_observe

    ssum = sum(range(1, size + 1))
    h_big = hvd_mod.allreduce_async(
        jnp.full((BIG,), float(rank + 1), jnp.float32),
        average=False, name="ov.big")
    _t.sleep(issue_wait)  # let the big negotiate in its own cycle

    for i in range(3):
        out = hvd_mod.synchronize(hvd_mod.allreduce_async(
            jnp.full((4,), float(rank + 1 + i), jnp.float32),
            average=False, name=f"ov.small.{i}"))
        np.testing.assert_allclose(np.asarray(out), ssum + i * size)
    # the smalls completed end-to-end while the big is still in flight
    assert not hvd_mod.poll(h_big), \
        "big collective completed before its delay - no overlap proven"
    np.testing.assert_allclose(
        np.asarray(hvd_mod.synchronize(h_big)), ssum)

    hvd_mod.shutdown()  # flush the timeline writer
    if rank != 0:
        return
    from tests.trace_utils import (
        collective_span, load_trace, negotiate_start_ts,
    )
    _, by_name = load_trace(os.environ["HOROVOD_TIMELINE"])
    c_start, c_end = collective_span(by_name["ov.big"])
    assert c_end - c_start >= 0.8 * delay * 1e6, (c_start, c_end, delay)
    for i in range(3):
        neg = negotiate_start_ts(by_name[f"ov.small.{i}"])
        assert c_start < neg < c_end, (i, c_start, neg, c_end)


def scenario_xla_ragged_allgather(hvd_mod, rank, size):
    """Heavy dim-0 skew (one big rank, the rest tiny) must flip the
    fused allgather onto the masked-psum rendering — wire bytes track
    the true payload like MPI_Allgatherv (reference:
    mpi_operations.cc:95-173) — and still return exact rank-ordered
    rows; mild skew must stay on the padded all_gather."""
    jax = _init_jax_distributed(rank, size)
    import jax.numpy as jnp
    from horovod_tpu.common import basics as _b

    # skewed: rank 0 contributes 64 rows, everyone else 1
    rows = 64 if rank == 0 else 1
    x = jnp.full((rows, 3), float(rank), jnp.float32)
    out = hvd_mod.allgather(x, name="rag.skew")
    expected = np.concatenate(
        [np.full((64 if r == 0 else 1, 3), float(r), np.float32)
         for r in range(size)])
    np.testing.assert_allclose(np.asarray(out), expected)

    # uniform: stays on the padded all_gather path
    u = hvd_mod.allgather(
        jnp.full((2, 3), float(rank), jnp.float32), name="rag.uni")
    np.testing.assert_allclose(
        np.asarray(u),
        np.concatenate([np.full((2, 3), float(r), np.float32)
                        for r in range(size)]))

    # bool under the same skew: the psum rendering promotes to int
    # internally and must cast back — output dtype and values exact
    b = hvd_mod.allgather(
        jnp.full((rows, 2), rank % 2 == 0, jnp.bool_), name="rag.bool")
    assert np.asarray(b).dtype == np.bool_, np.asarray(b).dtype
    np.testing.assert_array_equal(
        np.asarray(b),
        np.concatenate([np.full((64 if r == 0 else 1, 2), r % 2 == 0,
                                np.bool_) for r in range(size)]))

    rt = _b.runtime()
    xla = [b for b in rt.op_manager._backends if b.name == "xla_mesh"][0]
    kinds = {k[0] for k in xla._cache}
    assert "allgather_psum" in kinds, kinds   # skewed case used psum
    assert "allgather" in kinds, kinds        # uniform case stayed padded


def scenario_xla_hierarchical(hvd_mod, rank, size):
    """HOROVOD_HIERARCHICAL_ALLREDUCE: allreduce rides the factored
    (cross, local) mesh (all ranks share this host -> cross=1,
    local=size; the factored-psum code path still executes)."""
    jax = _init_jax_distributed(rank, size)
    import jax.numpy as jnp
    from horovod_tpu.common import basics as _b

    x = jnp.full((6,), float(rank + 1), jnp.float32)
    out = hvd_mod.allreduce(x, average=True, name="hier.ar")
    np.testing.assert_allclose(np.asarray(out),
                               sum(range(1, size + 1)) / size)
    # the 2D mesh must actually have been built
    rt = _b.runtime()
    xla = [b for b in rt.op_manager._backends
           if b.name == "xla_mesh"][0]
    assert xla._mesh2d is not None, "hierarchical mesh not built"


def scenario_xla_hier_allreduce_multihost(hvd_mod, rank, size):
    """HOROVOD_HIERARCHICAL_ALLREDUCE on a forced 2-host topology
    (2 ranks per fake host): the factored (cross, local) psum must be
    the executable that actually compiled — a real two-level reduction,
    not the degenerate cross_size==1 shape — and values must match the
    flat path exactly (reference: NCCLHierarchicalAllreduce,
    nccl_operations.cc:167-372)."""
    assert size == 4, "scenario expects 4 ranks"
    jax = _init_jax_distributed(rank, size)
    import jax.numpy as jnp
    from horovod_tpu.common import basics as _b

    # exactly-representable values: the sum is bit-exact in f32
    # regardless of reduction order, so this matches the flat path
    # bit-for-bit.
    x = jnp.full((6,), float(2 ** rank), jnp.float32)
    out = hvd_mod.allreduce(x, average=False, name="hm.ar")
    expected = float(sum(2 ** r for r in range(size)))
    assert np.asarray(out).tolist() == [expected] * 6, np.asarray(out)

    # integer dtype: bitwise-exact by construction
    xi = np.full((5,), rank + 1, np.int32)
    outi = hvd_mod.allreduce(jnp.asarray(xi), average=False,
                             name="hm.ari")
    assert np.asarray(outi).tolist() == [10] * 5

    rt = _b.runtime()
    xla = [b for b in rt.op_manager._backends if b.name == "xla_mesh"][0]
    assert xla._mesh2d is not None, "hierarchical mesh not built"
    assert xla._mesh2d.shape["cross"] == 2 and \
        xla._mesh2d.shape["local"] == 2, dict(xla._mesh2d.shape)
    # the compiled executables must be the (cross, local) factored ones
    ar_axes = {k[4] for k in xla._cache if k[0] == "allreduce"}
    assert ("cross", "local") in ar_axes, ar_axes
    assert all(a == ("cross", "local") for a in ar_axes), ar_axes


def scenario_xla_hierarchical_allgather(hvd_mod, rank, size):
    """HOROVOD_HIERARCHICAL_ALLGATHER on a forced 2-host topology
    (HOROVOD_HOSTNAME set by the harness: ranks 0,1 on hostA; 2,3 on
    hostB): variable-dim0 allgather must take the two-level
    local-gather -> cross-exchange path and still return rank-ordered
    rows (reference: MPIHierarchicalAllgather,
    mpi_operations.cc:179-329)."""
    assert size == 4, "scenario expects 4 ranks"
    jax = _init_jax_distributed(rank, size)
    import jax.numpy as jnp
    from horovod_tpu.common import basics as _b

    # variable dim0: rank r contributes r+1 rows valued r
    x = jnp.full((rank + 1, 3), float(rank), jnp.float32)
    out = hvd_mod.allgather(x, name="hier.ag")
    expected = np.concatenate(
        [np.full((r + 1, 3), float(r), np.float32) for r in range(size)])
    np.testing.assert_allclose(np.asarray(out), expected)

    # FUSED multi-entry allgather on the two-level path: several
    # variable-dim0 gathers submitted together must land in one
    # (cross, local) gather and unpack per entry in rank order
    seen = _record_batches(hvd_mod)
    hs = [hvd_mod.allgather_async(
        jnp.full((rank + 1 + (i % 2), i + 1), float(rank * 10 + i),
                 jnp.float32), name=f"hier.fag.{i}") for i in range(4)]
    for i, h in enumerate(hs):
        got = np.asarray(hvd_mod.synchronize(h))
        off = 0
        for r in range(size):
            rr = r + 1 + (i % 2)
            np.testing.assert_allclose(
                got[off:off + rr],
                np.full((rr, i + 1), float(r * 10 + i)))
            off += rr
    fag_batches = [n for k, n in seen if k == "ALLGATHER"]
    assert any(len(b) >= 2 for b in fag_batches), fag_batches

    rt = _b.runtime()
    xla = [b for b in rt.op_manager._backends if b.name == "xla_mesh"][0]
    assert xla._mesh2d is not None, "hierarchical mesh not built"
    assert xla._mesh2d.shape["cross"] == 2 and \
        xla._mesh2d.shape["local"] == 2, dict(xla._mesh2d.shape)
    kinds = {k[0] for k in xla._cache}
    assert "allgather_hier" in kinds, kinds
    assert "allgather" not in kinds, kinds


def scenario_lockcheck_inversion(hvd, rank, size):
    """HOROVOD_TPU_LOCKCHECK armed world (the mp default): the
    runtime's instrumented locks must survive a real collective with
    zero false inversions, and a deliberately inverted synthetic pair
    must raise LockInversionError naming both orders — every rank."""
    from horovod_tpu.common import lockdep

    assert lockdep.enabled(), "mp worlds must arm HOROVOD_TPU_LOCKCHECK"
    before = lockdep.inversion_count()

    # real work first: the armed instrumentation must be invisible
    x = np.full(64, float(rank + 1), np.float64)
    out = hvd.allreduce(x, average=False, name="lc.warm")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))
    assert lockdep.inversion_count() == before, \
        "healthy collective produced a lock inversion"

    # the runtime's core locks really are checked locks in this world
    from horovod_tpu.common import basics as _b
    tt_lock = _b.runtime().tensor_table._lock
    assert type(tt_lock).__name__ == "_CheckedLock", type(tt_lock)

    a = lockdep.lock("mp.sync.A")
    b = lockdep.lock("mp.sync.B")
    with a:
        with b:
            pass
    raised = False
    try:
        with b:
            with a:
                pass
    except lockdep.LockInversionError as e:
        raised = True
        assert "mp.sync.A" in str(e) and "mp.sync.B" in str(e), e
    assert raised, "inverted acquisition did not raise"
    assert lockdep.inversion_count() == before + 1

    # the world is still healthy after the caught inversion
    out = hvd.allreduce(x, average=False, name="lc.after")
    np.testing.assert_allclose(out, sum(range(1, size + 1)))


# -- elastic worlds (HOROVOD_ELASTIC=1; common/elastic.py) -------------
# A rank dies mid-collective; instead of the PR 2 fail-fast death
# sentence, the survivors re-rendezvous into a shrunk world and keep
# training. Victims die by fault injection (HOROVOD_FAULT_SPEC, set by
# the pytest wrappers); everything below asserts EXACT allreduce
# values against the current world size, so a post-resize step is
# bit-for-bit what a fresh world of that size would compute.

def _elastic_grad(b: int, ws_rank: int, n: int = 16) -> np.ndarray:
    """Deterministic integer-valued 'gradient': rank- and batch-
    dependent, so world sums are exactly computable for any size."""
    return np.full(n, float((ws_rank + 1) * (b % 7 + 1)), np.float32)


def _elastic_expected(b: int, ws: int, n: int = 16) -> np.ndarray:
    return np.full(n, float(sum(range(1, ws + 1)) * (b % 7 + 1)),
                   np.float32)


def _elastic_train(hvd, state, total: int, meta: dict):
    """The shared elastic training loop: one named steady allreduce
    per batch, params accumulated, batch committed. ``meta`` tracks
    world-size transitions, post-resize step counts and the recovery
    wall time (end of last good step -> end of resync)."""
    import time
    from horovod_tpu.common import elastic

    @elastic.run
    def train(state):
        while state.batch < total:
            ws = hvd.size()
            if meta["last_ws"] is None:
                meta["last_ws"] = ws
            elif ws != meta["last_ws"]:
                meta["resizes"].append((meta["last_ws"], ws,
                                        state.batch))
                if meta["t_last"] is not None:
                    meta["recovery_s"] = \
                        time.monotonic() - meta["t_last"]
                meta["last_ws"] = ws
            g = hvd.allreduce(_elastic_grad(state.batch, hvd.rank()),
                              average=False, name="eg")
            np.testing.assert_array_equal(
                g, _elastic_expected(state.batch, ws))
            state.params = state.params + g
            state.batch += 1
            state.commit()
            meta["t_last"] = time.monotonic()
            if meta["resizes"]:
                meta["post"] += 1

    train(state)


def _elastic_assert_world_coherent(hvd, state):
    """Every member's params must be identical after the run."""
    rows = hvd.allgather(state.params.reshape(1, -1), name="efp")
    for i in range(1, rows.shape[0]):
        np.testing.assert_array_equal(rows[i], rows[0])


def scenario_elastic_shrink(hvd, rank, size):
    """SIGKILL one rank mid-collective (fault spec set by the test):
    survivors re-rendezvous into ws-1, complete >= 20 more EXACT
    collectives (each equal to what a fresh shrunk world computes —
    the 'loss trajectory matches a never-killed world after resync'
    check), within 2x the heartbeat timeout, and end with identical
    params everywhere."""
    from horovod_tpu.common import elastic

    victim = size - 1
    hb = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"])
    total = 40
    state = elastic.State(params=np.zeros(16, np.float32), batch=0)
    meta = {"last_ws": None, "t_last": None, "recovery_s": None,
            "post": 0, "resizes": []}
    _elastic_train(hvd, state, total, meta)

    ctx = elastic.context()
    assert ctx is not None
    assert hvd.size() == size - 1, hvd.size()
    assert len(meta["resizes"]) == 1 \
        and meta["resizes"][0][:2] == (size, size - 1), meta["resizes"]
    assert meta["post"] >= 20, meta
    assert ctx.membership.generation == 1, ctx.membership.generation
    assert meta["recovery_s"] is not None \
        and meta["recovery_s"] < 2 * hb, meta["recovery_s"]
    # the dead member is on the world-converged blacklist, attributed
    assert any(f"rank {victim}" in entry
               for entry in ctx.membership.blacklist), \
        ctx.membership.blacklist
    m = hvd.metrics()
    if m["enabled"]:
        # resize history rides the PR 4 plane: the local snapshot
        # shows the shrunk world everywhere, and the coordinator's
        # own counters record the barrier it ran
        assert m["local"]["hvd_world_size"]["v"] == size - 1, \
            m["local"]["hvd_world_size"]
        if hvd.rank() == 0:
            assert m["local"]["hvd_world_resizes_total"]["v"] >= 1, \
                m["local"].get("hvd_world_resizes_total")
    _elastic_assert_world_coherent(hvd, state)


def scenario_elastic_coordinator_death(hvd, rank, size):
    """SIGKILL rank 0 — coordinator AND controller socket. The lowest
    surviving rank (old rank 1) must win the deterministic election,
    run the barrier, and host the new world's controller; training
    continues exactly in the shrunk world."""
    from horovod_tpu.common import elastic

    old_rank = rank
    total = 40
    state = elastic.State(params=np.zeros(16, np.float32), batch=0)
    meta = {"last_ws": None, "t_last": None, "recovery_s": None,
            "post": 0, "resizes": []}
    _elastic_train(hvd, state, total, meta)

    ctx = elastic.context()
    assert hvd.size() == size - 1, hvd.size()
    assert meta["post"] >= 20, meta
    # dense re-ranking: old rank r -> new rank r-1; old rank 1 is the
    # re-elected coordinator
    assert hvd.rank() == old_rank - 1, (old_rank, hvd.rank())
    assert ctx.membership.generation == 1
    assert any("rank 0" in entry for entry in ctx.membership.blacklist)
    _elastic_assert_world_coherent(hvd, state)


def scenario_elastic_double_fault(hvd, rank, size):
    """Two-stage failure: one rank SIGKILLed mid-collective, a SECOND
    rank SIGKILLed on entry to the re-rendezvous barrier (fault
    trigger rdzv=1). The barrier must wait out its window for the
    silent second victim and close with the remaining survivors —
    recovery survives a fault DURING recovery."""
    from horovod_tpu.common import elastic

    total = 30
    state = elastic.State(params=np.zeros(16, np.float32), batch=0)
    meta = {"last_ws": None, "t_last": None, "recovery_s": None,
            "post": 0, "resizes": []}
    _elastic_train(hvd, state, total, meta)

    ctx = elastic.context()
    assert hvd.size() == size - 2, hvd.size()
    assert meta["post"] >= 10, meta
    assert ctx.membership.generation == 1
    assert len(ctx.membership.blacklist) == 2, ctx.membership.blacklist
    _elastic_assert_world_coherent(hvd, state)


def scenario_elastic_rejoin(hvd, rank, size):
    """Shrink, then GROW back: one rank is SIGKILLed, the survivors
    re-form at ws-1, and the (old) rank 0 respawns a fresh joiner
    process which rejoins at the next rendezvous barrier, resyncs the
    State by broadcast, and trains to completion in lockstep. Also
    runs as the JOINER itself (spawned with HOROVOD_ELASTIC_JOIN=1)."""
    import subprocess
    import sys as _sys
    import time
    from horovod_tpu.common import elastic

    ctx = elastic.context()
    joiner = ctx is not None and ctx.joined_as_rejoiner
    total = 50
    state = elastic.State(params=np.zeros(16, np.float32), batch=0)
    meta = {"last_ws": None, "t_last": None, "recovery_s": None,
            "post": 0, "resizes": []}
    child = {}

    from horovod_tpu.common import elastic as _e

    @_e.run
    def train(state):
        # Lockstep predicate shared by survivors AND the joiner: keep
        # training until the batch budget is spent AND the world has
        # grown back — every member sees the same (synced batch,
        # world size) pair, so everyone exits the same iteration.
        while state.batch < total or hvd.size() < size:
            ws = hvd.size()
            if meta["last_ws"] is None:
                meta["last_ws"] = ws
            elif ws != meta["last_ws"]:
                meta["resizes"].append((meta["last_ws"], ws,
                                        state.batch))
                meta["last_ws"] = ws
            if not joiner and hvd.rank() == 0 and ws == size - 1 \
                    and "proc" not in child:
                # The supervision-loop stand-in: respawn the lost slot
                # as a joiner pointed at this rank's elastic listener.
                env = dict(os.environ)
                env.pop("HOROVOD_FAULT_SPEC", None)
                env["HOROVOD_ELASTIC_JOIN"] = "1"
                env["HOROVOD_ELASTIC_JOIN_ADDR"] = "127.0.0.1"
                env["HOROVOD_ELASTIC_JOIN_PORT"] = str(ctx.port)
                child["proc"] = subprocess.Popen(
                    [_sys.executable, "-m", "tests.mp_scenarios",
                     "elastic_rejoin", "9", str(size), "0"], env=env)
            g = hvd.allreduce(_elastic_grad(state.batch, hvd.rank()),
                              average=False, name="eg")
            np.testing.assert_array_equal(
                g, _elastic_expected(state.batch, hvd.size()))
            state.params = state.params + g
            state.batch += 1
            state.commit()
            if meta["resizes"]:
                meta["post"] += 1

    train(state)

    assert hvd.size() == size, (hvd.size(), size)  # grown back
    ctx2 = elastic.context()
    if joiner:
        assert ctx2.joined_as_rejoiner
        assert ctx2.membership.generation >= 2
    else:
        # shrink first; the grow transition may land exactly on the
        # loop-exit edge (survivors can finish the batch budget while
        # the joiner is still starting up), so assert it through the
        # final world state rather than an observed body iteration.
        assert meta["resizes"] and \
            meta["resizes"][0][:2] == (size, size - 1), meta["resizes"]
        assert ctx2.membership.generation == 2, \
            ctx2.membership.generation
        if hvd.rank() == 0:
            assert ctx2.rejoins_admitted == 1, ctx2.rejoins_admitted
    _elastic_assert_world_coherent(hvd, state)
    if "proc" in child:
        rc = child["proc"].wait(timeout=60)
        assert rc == 0, f"joiner exited {rc}"


def scenario_elastic_disabled_fail_fast(hvd, rank, size):
    """Without HOROVOD_ELASTIC, elastic.run is a transparent wrapper:
    the PR 2 WorldAbortedError propagates verbatim — fail-fast
    behavior unchanged."""
    from horovod_tpu.common import elastic
    from horovod_tpu.common.status import WorldAbortedError

    assert elastic.context() is None
    state = elastic.State(params=np.zeros(8, np.float32), batch=0)

    @elastic.run
    def train(state):
        while state.batch < 1000:
            hvd.allreduce(np.ones(8, np.float32), average=False,
                          name="eg")
            state.batch += 1

    try:
        train(state)
        raise AssertionError("fault-injected world must abort")
    except WorldAbortedError as e:
        assert e.origin_rank == 1, e


def scenario_selfop_preempt(hvd, rank, size):
    """Proactive drain on a preemption notice (common/selfop.py): a
    ``preempt`` fault SIGTERMs one rank mid-training with a grace
    window. The supervision tick on that rank turns the notice into a
    resolved world abort, the rank drains to its last commit and
    retires with exit 0 (never reaching the post-train asserts), and
    the SURVIVORS resize to ws-1 with zero lost steps — every
    post-resize collective bit-exact vs a fresh shrunk world — all
    inside the grace window, before the SIGKILL backstop."""
    from horovod_tpu.common import elastic, selfop

    victim = size - 1
    hb = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"])
    # a batch costs >= 1 negotiation cycle, so the cycle-40 fault
    # lands before batch 40 and >= 40 post-resize batches remain
    total = 80
    state = elastic.State(params=np.zeros(16, np.float32), batch=0)
    meta = {"last_ws": None, "t_last": None, "recovery_s": None,
            "post": 0, "resizes": []}
    _elastic_train(hvd, state, total, meta)

    # The preempted rank exits 0 inside the wrapper (retire path) —
    # only survivors get here.
    assert rank != victim, "preempted rank must retire before this"
    ctx = elastic.context()
    assert hvd.size() == size - 1, hvd.size()
    assert len(meta["resizes"]) == 1 \
        and meta["resizes"][0][:2] == (size, size - 1), meta["resizes"]
    assert meta["post"] >= 20, meta
    assert ctx.membership.generation == 1, ctx.membership.generation
    assert meta["recovery_s"] is not None \
        and meta["recovery_s"] < 2 * hb, meta["recovery_s"]
    # the resize is ATTRIBUTED to the supervision policy, not to a
    # death: the world-converged cause names the drain
    assert "selfop-preempt" in ctx.last_resize_cause, \
        ctx.last_resize_cause
    assert any(f"rank {victim}" in entry
               for entry in ctx.membership.blacklist), \
        ctx.membership.blacklist
    # the verdict plane rode the rendezvous on every member: a resize
    # with no pending demotion installs the EMPTY verdict for this
    # generation (stale pacing cannot leak across resizes)
    v = selfop.verdict()
    assert v.kind == "" and v.generation == 1, (v.kind, v.generation)
    assert selfop.cycle_pace_s(hvd.rank()) == 0.0
    m = hvd.metrics()
    if m["enabled"]:
        assert m["local"]["hvd_world_size"]["v"] == size - 1, \
            m["local"]["hvd_world_size"]
    _elastic_assert_world_coherent(hvd, state)


def scenario_selfop_demote(hvd, rank, size):
    """Telemetry-driven demotion (common/selfop.py): a persistent
    ``delay`` fault makes one launch rank the habitual last-arriver.
    After the churn cooldown the coordinator's supervision policy
    reads the straggler attribution window, demotes that rank to the
    ring tail via a same-size resize, and every member installs the
    identical demote verdict (world-replicated) with a pacing hint.
    Post-resize, non-demoted ranks pace their cycle top and the
    demoted rank's last-arriver share drops below the trigger —
    the skew measurably improves."""
    import re as _re
    import time

    from horovod_tpu.common import basics as _b
    from horovod_tpu.common import elastic, selfop

    old_rank = rank
    straggler = 1  # launch rank carrying the delay fault
    state = elastic.State(params=np.zeros(16, np.float32), batch=0)
    meta = {"post": 0}

    @elastic.run
    def train(state):
        # Lockstep predicate: the verdict installs at the SAME resize
        # on every member and training resumes from the same commit,
        # so the post-demotion counter stays identical everywhere and
        # every rank exits the same iteration. Keep the post window
        # under the 5s churn cooldown so no second verdict can fire.
        while True:
            if selfop.verdict().kind == "demote":
                meta["post"] += 1
                if meta["post"] > 60:
                    break
            elif state.batch > 4000:
                raise AssertionError(
                    f"no demotion after {state.batch} batches")
            g = hvd.allreduce(_elastic_grad(state.batch, hvd.rank()),
                              average=False, name="eg")
            np.testing.assert_array_equal(
                g, _elastic_expected(state.batch, hvd.size()))
            state.params = state.params + g
            state.batch += 1
            state.commit()

    train(state)

    ctx = elastic.context()
    assert hvd.size() == size, hvd.size()  # same size, reordered
    assert ctx.membership.generation == 1, ctx.membership.generation
    assert "selfop-demote" in ctx.last_resize_cause, \
        ctx.last_resize_cause
    # every member holds the IDENTICAL verdict (world-replicated)
    v = selfop.verdict()
    assert v.kind == "demote", v.kind
    assert v.target_rank == size - 1, v.target_rank  # ring tail
    assert v.pace_us > 0, v.pace_us
    assert v.generation == 1, v.generation
    rows = hvd.allgather(
        np.array([[v.target_rank, v.pace_us, v.generation]],
                 dtype=np.int64), name="sd.v")
    for i in range(1, size):
        np.testing.assert_array_equal(rows[i], rows[0])
    # dense renumbering: the straggler moved to the tail, everyone
    # after it shifted down one, everyone before it kept their rank
    if old_rank == straggler:
        assert hvd.rank() == size - 1, hvd.rank()
    elif old_rank > straggler:
        assert hvd.rank() == old_rank - 1, (old_rank, hvd.rank())
    else:
        assert hvd.rank() == old_rank, (old_rank, hvd.rank())
    # pacing applies to every member EXCEPT the demoted tail
    pace = selfop.cycle_pace_s(hvd.rank())
    if hvd.rank() == size - 1:
        assert pace == 0.0, pace
    else:
        assert pace > 0.0, pace
    if hvd.rank() == 0:
        assert selfop.decision_counts().get("demote") == 1, \
            selfop.decision_counts()
        # skew improves: the pre-demotion last-arriver share is in the
        # policy's decision line; the post-resize attribution window
        # (fresh tracker, >= 60 paced gathers) must show the demoted
        # rank below it — and below the trigger threshold
        pol = selfop.policy()
        m = _re.search(r"share=([0-9.]+)", pol._last_line)
        assert m, pol._last_line
        share_pre = float(m.group(1))
        assert share_pre >= 0.6, share_pre
        stats = _b.runtime()._straggler.window_stats()
        window = stats["window"]
        assert window >= 40, stats
        share_post = stats["last_counts"].get(size - 1, 0) / window
        assert share_post < share_pre, (share_post, share_pre, stats)
        assert share_post < 0.6, (share_post, stats)
    _elastic_assert_world_coherent(hvd, state)


# ---------------------------------------------------------------------------
# Multi-tenant collective service (common/tenancy.py,
# docs/multitenancy.md): concurrent sub-worlds on one fleet under QoS
# scheduling, fault isolation between tenants, and service-mode
# attach/detach with the parameter-snapshot broadcast fanout.
# ---------------------------------------------------------------------------

def _tenant_steps(tenant, rank, size, key, steps, numel=32):
    """Drive ``steps`` deterministic allreduces on ``tenant`` and
    assert exactness per step; returns the outputs."""
    ssum = sum(range(1, size + 1))
    outs = []
    for i in range(steps):
        out = tenant.allreduce(
            np.full(numel, float(rank + 1) * (i + 1), np.float32),
            average=False, name=f"{key}.g")
        np.testing.assert_allclose(out, ssum * (i + 1))
        outs.append(np.asarray(out))
    return outs


def scenario_tenants_exact(hvd, rank, size):
    """Two equal-weight tenants spanning the SAME ws=4 fleet train
    concurrently from separate threads; each tenant's per-step results
    are exact, and tenant A's sequence replayed AFTER the concurrent
    phase (B idle) is bit-identical — co-tenancy never perturbs
    numerics. Also asserts the per-tenant observability surfaces."""
    import threading
    ta = hvd.create_tenant("jobA", list(range(size)))
    tb = hvd.create_tenant("jobB", list(range(size)))
    assert ta.rank == rank and ta.size == size
    assert ta.world_id != tb.world_id
    results = {}

    def run(t, key):
        results[key] = _tenant_steps(t, rank, size, key, 30)

    threads = [threading.Thread(target=run, args=(t, k))
               for t, k in ((ta, "a"), (tb, "b"))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results["a"]) == 30 and len(results["b"]) == 30

    # single-tenant replay of A's exact submission sequence, B idle:
    # bit-identical outputs prove scheduling never touched the math
    ssum = sum(range(1, size + 1))
    for i in range(30):
        out = ta.allreduce(
            np.full(32, float(rank + 1) * (i + 1), np.float32),
            average=False, name="replay.g")
        assert (np.asarray(out) == results["a"][i]).all(), i
        np.testing.assert_allclose(out, ssum * (i + 1))

    # per-tenant observability: lane stats flow, and the stall-report
    # world line carries the tenant identity + scheduler verdicts
    for t, key in ((ta, "jobA"), (tb, "jobB")):
        stats = t.lane_stats()
        assert stats["cycles"] >= 30, (key, stats)
        line = t._runtime._world_status_line()
        assert f"tenant {key}" in line and "weight" in line, line
    # the default world is untouched by tenant traffic
    out = hvd.allreduce(np.full(4, float(rank), np.float64),
                        average=False, name="dflt")
    np.testing.assert_allclose(out, sum(range(size)))
    ta.shutdown()
    tb.shutdown()


def scenario_tenants_tp_dp(hvd, rank, size):
    """A TENSOR-parallel tenant and a DATA-parallel tenant sharing one
    ws=4 fleet (the parallel-strategy composition ROADMAP names as
    unlocked by tenancy): the TP tenant drives Megatron-style
    row-parallel partial-sum allreduces plus column-parallel
    allgathers, the DP tenant drives averaged gradient allreduces.
    Both run concurrently from separate threads; every step of each is
    EXACT (integer-valued operands make float order irrelevant), and
    QoS isolation holds: each lane accounts its own cycles, the TP
    sequence replayed solo after the concurrent phase is bit-identical
    (co-scheduling never perturbed the math), and the default world is
    untouched."""
    import threading
    tp = hvd.create_tenant("tp", list(range(size)), weight=2.0)
    dp = hvd.create_tenant("dp", list(range(size)))
    assert tp.world_id != dp.world_id
    steps = 20
    # integer-valued operands: partial products and sums are exact in
    # f32 no matter the reduction order
    rng = np.random.RandomState(123)  # same seed on every rank
    A = rng.randint(-3, 4, size=(4, 8)).astype(np.float32)
    B = rng.randint(-3, 4, size=(8, 6)).astype(np.float32)
    assert 8 % size == 0 and 6 % 3 == 0
    k = 8 // size  # row-parallel contraction shard
    want_full = A @ B
    results = {"tp": [], "dp": []}

    def run_tp():
        for i in range(steps):
            # row-parallel: each rank holds a K-shard of the
            # contraction; the allreduce-sum of partials IS the matmul
            part = (A[:, rank * k:(rank + 1) * k]
                    @ B[rank * k:(rank + 1) * k, :]) * (i + 1)
            out = tp.allreduce(part, average=False, name="tp.row")
            np.testing.assert_array_equal(
                np.asarray(out), want_full * (i + 1))
            results["tp"].append(np.asarray(out))
            # column-parallel: activations gathered along features
            g = tp.allgather(
                np.full((2, 3), float(rank * 10 + i), np.float32),
                name="tp.col")
            g = np.asarray(g)
            assert g.shape == (2 * size, 3)
            np.testing.assert_array_equal(
                g, np.repeat(np.arange(size) * 10.0 + i, 2)
                .astype(np.float32)[:, None] * np.ones(3, np.float32))

    def run_dp():
        for i in range(steps):
            # gradient averaging: mean over ranks, exact for /4
            grad = np.full(64, float((rank + 1) * (i + 1)), np.float32)
            out = dp.allreduce(grad, average=True, name="dp.grad")
            want = sum(range(1, size + 1)) * (i + 1) / size
            np.testing.assert_array_equal(np.asarray(out), want)
            results["dp"].append(np.asarray(out))

    threads = [threading.Thread(target=run_tp),
               threading.Thread(target=run_dp)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results["tp"]) == steps and len(results["dp"]) == steps

    # QoS isolation: per-lane accounting is independent (each lane saw
    # at least its own steps' cycles), and the scheduler's status
    # surface names both tenants with their weights
    for t, key in ((tp, "tp"), (dp, "dp")):
        stats = t.lane_stats()
        assert stats["cycles"] >= steps, (key, stats)
        line = t._runtime._world_status_line()
        assert f"tenant {key}" in line and "weight" in line, line

    # solo replay of the TP sequence (DP idle) is bit-identical:
    # co-tenancy never perturbed the numerics
    for i in range(steps):
        part = (A[:, rank * k:(rank + 1) * k]
                @ B[rank * k:(rank + 1) * k, :]) * (i + 1)
        out = tp.allreduce(part, average=False, name="tp.replay")
        assert (np.asarray(out) == results["tp"][i]).all(), i

    # the default world is untouched by tenant traffic
    out = hvd.allreduce(np.full(4, float(rank), np.float64),
                        average=False, name="tpdp.dflt")
    np.testing.assert_allclose(out, sum(range(size)))
    tp.shutdown()
    dp.shutdown()


def scenario_tenants_priority(hvd, rank, size):
    """3:1 weights must skew the contended cycle share toward the
    heavy tenant: when the heavy tenant finishes its fixed workload,
    the equal-sized light workload is measurably behind, and the
    light lane records real deferrals. Submissions ride a small async
    pipeline so both lanes stay backlogged."""
    import threading
    heavy = hvd.create_tenant("heavy", list(range(size)), weight=3.0)
    light = hvd.create_tenant("light", list(range(size)), weight=1.0)
    n, depth = 400, 4
    ssum = sum(range(1, size + 1))
    light_done_at_heavy_done = [None]

    def run(t, key):
        pend = []
        for i in range(n):
            pend.append(t.allreduce_async(
                np.full(16, float(rank + 1), np.float32),
                average=False, name=f"{key}.g{i % depth}"))
            if len(pend) >= depth:
                np.testing.assert_allclose(
                    t.synchronize(pend.pop(0)), ssum)
        while pend:
            np.testing.assert_allclose(t.synchronize(pend.pop(0)),
                                       ssum)
        if key == "h":
            light_done_at_heavy_done[0] = \
                light.lane_stats()["cycles"]

    threads = [threading.Thread(target=run, args=(t, k))
               for t, k in ((heavy, "h"), (light, "l"))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    h_cycles = heavy.lane_stats()["cycles"]
    l_at_h = light_done_at_heavy_done[0]
    # the heavy tenant held a strictly larger share of the contended
    # window (equal weights measure ~1.0 here; 3:1 measures ~1.5 on
    # this host since granted cycles still overlap — the quantitative
    # bar lives in collective_bench --multitenant; a loaded CI host
    # adds variance, so the gate here is the DIRECTION with margin
    # and a world-total deferral proof)
    assert l_at_h < 0.9 * h_cycles, (l_at_h, h_cycles)
    world_deferrals = float(np.asarray(light.allreduce(
        np.asarray([float(light.lane_stats()["deferrals"])],
                   np.float32),
        average=False, name="l.defer"))[0])
    assert world_deferrals > 0, light.lane_stats()
    heavy.shutdown()
    light.shutdown()


def scenario_tenants_quota(hvd, rank, size):
    """A cycles/sec quota defers the over-quota tenant — it crawls at
    the budget but every cycle completes exactly (deferred, never
    corrupted) while the unlimited co-tenant runs at full speed."""
    import threading
    import time as _time
    fast = hvd.create_tenant("fast", list(range(size)))
    capped = hvd.create_tenant("capped", list(range(size)),
                               quota_cycles_s=10.0)
    timing = {}

    def run(t, key, steps):
        t0 = _time.monotonic()
        _tenant_steps(t, rank, size, key, steps, numel=16)
        timing[key] = _time.monotonic() - t0

    threads = [threading.Thread(target=run, args=(fast, "f", 150)),
               threading.Thread(target=run, args=(capped, "c", 30))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    c = capped.lane_stats()
    # Deferral is observed per RANK; on a heavily loaded CI host one
    # rank's natural pace can fall under the quota (nothing for its
    # bucket to defer) — so assert on the WORLD total, with a
    # wall-time floor as the loaded-host fallback: 30 cycles at 10/s
    # minus the 1s burst bucket of 10 needs ~2s no matter what.
    world_deferrals = float(np.asarray(capped.allreduce(
        np.asarray([float(c["deferrals"])], np.float32),
        average=False, name="c.defer"))[0])
    assert world_deferrals > 0 or timing["c"] > 3.0, \
        (c, timing)
    assert timing["c"] > 1.4, timing
    # the unlimited tenant is not dragged to the capped tenant's
    # pace: compare PER-STEP pace, not total walls — the 5x larger
    # free workload racing the capped wall flakes on a loaded host
    # where raw step cost approaches the quota gap (brief fast
    # deferrals around the capped lane's refill instants are correct
    # weighted fairness, so deferral COUNTS are not compared)
    assert timing["f"] / 150 < (timing["c"] / 30) / 2, timing
    fast.shutdown()
    capped.shutdown()


def scenario_tenants_fault_isolation(hvd, rank, size):
    """SIGKILL of a rank inside tenant A ([0,1]) raises
    WorldAbortedError naming A's dead rank on A's survivor ONLY;
    tenant B ([2,3]) — disjoint ranks of the SAME launched fleet —
    trains to completion with exact results and never observes an
    abort."""
    import signal
    import time as _time
    from horovod_tpu.common.status import WorldAbortedError
    assert size == 4, "scenario expects 4 launched processes"
    ta = hvd.create_tenant("jobA", [0, 1])
    tb = hvd.create_tenant("jobB", [2, 3])
    if rank in (0, 1):
        assert ta is not None and tb is None
        assert ta.size == 2 and ta.rank == rank
        _tenant_steps(ta, ta.rank, 2, "a", 5, numel=16)
        if rank == 1:
            os.kill(os.getpid(), signal.SIGKILL)
        # survivor: drive tenant-A collectives until the fail-fast
        # abort surfaces, naming A's (tenant-local) rank 1
        t0 = _time.monotonic()
        i = 0
        while True:
            try:
                ta.allreduce(np.ones(16, np.float32), average=False,
                             name=f"a.post/{i}")
            except WorldAbortedError as e:
                assert e.origin_rank == 1, e
                break
            i += 1
            assert _time.monotonic() - t0 < 40.0, \
                "tenant A kept succeeding past its member's death"
        ta.shutdown()
        return
    # ranks 2, 3: tenant B must be completely unaffected — train
    # through the kill window and well past it
    assert tb is not None and ta is None
    assert tb.size == 2 and tb.rank == rank - 2
    for i in range(40):
        out = tb.allreduce(
            np.full(16, float(tb.rank + 1) * (i + 1), np.float32),
            average=False, name="b.g")
        np.testing.assert_allclose(out, 3.0 * (i + 1))
        _time.sleep(0.05)  # stretch across A's death + detection
    assert tb.alive, "tenant B's world must survive tenant A's abort"
    tb.shutdown()


def scenario_tenants_service(hvd, rank, size):
    """Service mode end to end on one launch: ranks 0-1 form a warm
    --service fleet (HOROVOD_TPU_SERVICE=1) that trains and publishes
    parameter snapshots; ranks 2-3 never join the fleet's world —
    they ATTACH as a 2-replica group, pull a snapshot through the
    broadcast fanout (gate → root → child), verify it, and DETACH.
    The fleet trains to completion without any re-rendezvous."""
    import time as _time
    assert size == 4, "scenario expects 4 launched processes"
    gate_port = int(os.environ["HOROVOD_TPU_SERVICE_PORT"])
    # The gate speaks the fleet's HMAC'd channel framing: an attaching
    # job must present the fleet's HOROVOD_SECRET_KEY (the service
    # plane shares the control plane's auth boundary — an unsecured
    # dialer is rejected at the first frame). The suite sometimes runs
    # with a secret inherited from the environment, so thread it.
    secret = os.environ.get("HOROVOD_SECRET_KEY", "").encode()
    if rank >= 2:
        # attach clients: no hvd.init() at all — a service job needs
        # only the gate endpoint (+ secret). Generous deadlines: under
        # a loaded CI host, interpreter+numpy startup alone can eat
        # tens of seconds before this line runs.
        from horovod_tpu.common import tenancy
        print(f"[client {rank}] dialing gate 127.0.0.1:{gate_port}",
              flush=True)
        rep = tenancy.attach("127.0.0.1", gate_port, "evaljob",
                             replica=rank - 2, group=2, timeout=90.0,
                             secret=secret)
        print(f"[client {rank}] lease {rep.lease} members "
              f"{rep.members}", flush=True)
        assert len(rep.members) == 2
        version, params = rep.fetch_snapshot(min_version=1,
                                             timeout=60.0)
        print(f"[client {rank}] snapshot v{version}", flush=True)
        assert version >= 1
        np.testing.assert_array_equal(
            params["w"], np.arange(16, dtype=np.float32) * version)
        assert int(params["step"][0]) == version * 10
        rep.detach()
        return
    # fleet ranks 0-1: a 2-rank world on the env endpoint
    hvd.init(comm=(rank, 2))
    from horovod_tpu.common import tenancy
    gate = tenancy.service_gate()
    if rank == 0:
        assert gate is not None and gate.port == gate_port
        print(f"[fleet 0] gate up on {gate.port} pid {os.getpid()}",
              flush=True)
    ssum = 3.0  # ranks contribute 1.0 and 2.0
    for step in range(1, 61):
        out = hvd.allreduce(np.full(8, float(rank + 1), np.float32),
                            average=False, name="fleet.g")
        np.testing.assert_allclose(out, ssum)
        if rank == 0 and step % 10 == 0:
            tenancy.publish_snapshot(
                {"w": np.arange(16, dtype=np.float32) * (step // 10),
                 "step": np.asarray([step], np.int64)},
                version=step // 10)
        _time.sleep(0.02)
    if rank == 0:
        # the fleet never re-rendezvoused: wait for both replicas to
        # have come AND gone (the gate runs on daemon threads beside
        # the world — no collective is needed to serve them, which is
        # the point). The window covers loaded-host client startup.
        deadline = _time.monotonic() + 120.0
        while _time.monotonic() < deadline:
            s = gate.stats()
            if s["attaches"] >= 2 and s["detaches"] >= 2:
                break
            _time.sleep(0.1)
        s = gate.stats()
        assert s["attaches"] >= 2 and s["detaches"] >= 2, s
        assert s["groups"] == {}, s
    # a final world collective proves the fleet world is still whole
    out = hvd.allreduce(np.full(4, float(rank + 1), np.float32),
                        average=False, name="fleet.final")
    np.testing.assert_allclose(out, ssum)


scenario_tenants_service.no_auto_init = True


# -- PR 16: batched reactor, native int8 codec, chunked relay ----------

def scenario_abort_sigkill_batched_gather(hvd, rank, size):
    """SIGKILL rank 1 while the coordinator sits in the BATCHED
    reactor gather (socket star, shm/ring off by the wrapper): the
    io_uring/poll batched submission must honor the same recv
    deadlines and heartbeat absorption as the sequential loop —
    survivors raise WorldAbortedError naming rank 1 within the
    detection deadline instead of hanging in the kernel."""
    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    _await_world_abort(hvd, rank, 1, deadline, "bg.sk")


def scenario_abort_sever_batched_gather(hvd, rank, size):
    """Fault-injected link severance mid-batched-gather: rank 1's
    upward channel dies abruptly (process alive), the coordinator's
    batched submission sees the EOF among its completions and must
    blame rank 1; the severed rank finds its own channel closed."""
    from horovod_tpu.common.status import HorovodInternalError

    victim = 1
    deadline = float(os.environ["HOROVOD_HEARTBEAT_TIMEOUT"]) + 12.0
    if rank == victim:
        try:
            while True:
                hvd.allreduce(np.ones(64, np.float32), average=False,
                              name="bg.sv")
        except HorovodInternalError:
            pass
        hvd.shutdown()
        return
    _await_world_abort(hvd, rank, victim, deadline, "bg.sv")


def scenario_reactor_exact(hvd, rank, size):
    """Reactor-knob sweep driver: a mixed-collective schedule
    (allreduce, allgather, reducescatter, broadcast, alltoall) whose
    rank-0 outputs land in HVD_REACTOR_OUT for the wrapper to
    byte-compare across worlds — HOROVOD_TPU_REACTOR is recv
    discipline only, so all-on, all-off and HETEROGENEOUS worlds must
    put the same bytes on the wire and compute identical results.
    With HVD_EXPECT_REACTOR=1 the coordinator additionally proves the
    batched path actually engaged (the A/B is not vacuous)."""
    rng = np.random.RandomState(7000 + rank)
    outs = []
    for step in range(6):
        x = rng.randn(1024).astype(np.float32)
        outs.append(np.asarray(
            hvd.allreduce(x, average=False, name=f"rx.{step}")))
    g = hvd.allgather(
        np.arange(6, dtype=np.float32).reshape(3, 2) + 100 * rank,
        name="rx.ag")
    outs.append(np.asarray(g).reshape(-1))
    rs = hvd.reducescatter(
        np.arange(size * 4, dtype=np.float32) * (rank + 1), name="rx.rs")
    outs.append(np.asarray(rs).reshape(-1))
    b = hvd.broadcast(np.full(33, float(rank), np.float32),
                      root_rank=size - 1, name="rx.bc")
    outs.append(np.asarray(b))
    a2a = hvd.alltoall(
        np.arange(size * 2, dtype=np.float32) + 100 * rank,
        name="rx.a2a")
    outs.append(np.asarray(a2a).reshape(-1))
    # pin correctness locally too, not just cross-world identity
    np.testing.assert_allclose(
        outs[-1], np.concatenate(
            [np.arange(rank * 2, (rank + 1) * 2) + 100 * src
             for src in range(size)]).astype(np.float32))
    np.testing.assert_allclose(b, float(size - 1))
    out_path = os.environ.get("HVD_REACTOR_OUT")
    if rank == 0 and out_path:
        np.save(out_path, np.concatenate([o.reshape(-1) for o in outs]))
    if os.environ.get("HVD_EXPECT_REACTOR") == "1" and rank == 0:
        from horovod_tpu import native as _nat
        if _nat.get() is not None:
            assert _metric_value(hvd, "hvd_reactor_batch_size") > 0, \
                "batched reactor never engaged on the coordinator"


def scenario_int8_codec_parity(hvd, rank, size):
    """Native-codec convergence parity driver: an int8+error-feedback
    steady loop (same fused batch every step, so the residual chain
    matters) whose outputs land in HVD_REACTOR_OUT. The wrapper runs
    this world twice — native codec vs HOROVOD_NATIVE=0 numpy codec —
    and compares byte-for-byte: hvd_quant8/hvd_dequant8 are
    BIT-IDENTICAL to the numpy reference, so swapping them changes
    nothing about training."""
    rng = np.random.RandomState(8000 + rank)
    outs = []
    for step in range(10):
        xs = [rng.randn(777).astype(np.float32),
              rng.randn(333).astype(np.float32)]
        got = hvd.grouped_allreduce(xs, average=False, name="i8")
        outs.extend(np.asarray(o) for o in got)
    # reducescatter rides the int8 star verdict too (PR 16 extension)
    rs = hvd.reducescatter(
        rng.randn(size * 8).astype(np.float32), name="i8.rs")
    outs.append(np.asarray(rs).reshape(-1))
    out_path = os.environ.get("HVD_REACTOR_OUT")
    if rank == 0 and out_path:
        np.save(out_path, np.concatenate([o.reshape(-1) for o in outs]))


# What a rank prints when it has run one of several scenarios to its
# end (``tests/test_multiprocess.py`` ``run_scenarios`` reads it).
SCENARIO_DONE = "scenario done:"


def main():
    """``python -m tests.mp_scenarios NAME[,NAME...] RANK SIZE
    PORT[,PORT...]``: the scenarios in turn in this interpreter, each
    between its own ``hvd.init()`` and ``hvd.shutdown()`` on its own
    port, a line on stdout for each that ran to its end. The first
    that raises ends the process: the ones after it never run, and
    print nothing."""
    scenarios, ports = sys.argv[1].split(","), sys.argv[4].split(",")
    rank, size = int(sys.argv[2]), int(sys.argv[3])
    assert len(scenarios) == len(ports), (scenarios, ports)
    # Hard in-process deadline (set by run_scenario slightly under its
    # subprocess timeout): a deadlocked rank dumps every thread's stack
    # and exits nonzero, so a regression that reintroduces a hang fails
    # fast WITH a diagnosis instead of eating the tier-1 time budget
    # and reporting only "timed out".
    deadline = float(os.environ.get("HOROVOD_TEST_DEADLINE", "0"))
    if deadline > 0:
        import faulthandler
        faulthandler.dump_traceback_later(deadline, exit=True)
    os.environ["HOROVOD_RANK"] = str(rank)
    os.environ["HOROVOD_SIZE"] = str(size)
    os.environ["HOROVOD_CONTROLLER_ADDR"] = "127.0.0.1"
    os.environ.setdefault("HOROVOD_CYCLE_TIME", "1")
    import horovod_tpu as hvd
    for scenario, port in zip(scenarios, ports):
        os.environ["HOROVOD_CONTROLLER_PORT"] = port
        fn = globals()[f"scenario_{scenario}"]
        if not getattr(fn, "no_auto_init", False):
            hvd.init()
        try:
            fn(hvd, rank, size)
        finally:
            hvd.shutdown()
        print(SCENARIO_DONE, scenario, flush=True)


if __name__ == "__main__":
    main()
