"""Collective-path benchmarks on a multi-process CPU world.

What the reference publishes as its value proposition is collective
efficiency (docs/benchmarks.md; README.md:66-70 scaling efficiency).
This bench measures THIS framework's full control+data path — enqueue →
negotiate (TCP controller) → fuse → execute → callback — with no
shortcuts, across its three host data planes:

  * ``shm``   — shared-memory segment, the default for same-host worlds
                (the TPU deployment shape: one process per chip);
  * ``star``  — TCP socket gather→sum@0→broadcast, the universal
                fallback (reference analog: MPI CPU ops);
  * ``ring``  — 2-phase TCP ring for large payloads on multi-host
                worlds (reference analog: MPI's internal ring
                algorithms inside MPI_Allreduce).

Timings are **medians** over ALLREDUCE_ITERS ops (p25/p75 recorded):
this host is a 1-vCPU VM with bursty external interference, and means
are dominated by the bad windows.

IMPORTANT CONTEXT FOR THE SCALING NUMBERS: with ``os.cpu_count() == 1``
an np=8 world time-shares one core, so the classic efficiency metric
steps_N / steps_1 is bounded above by cores/np (12.5% at np=8) for any
framework, with zero communication cost — 8x the compute now shares
one core. RESULTS_cpu.json therefore reports, alongside the raw
number:

  * ``timeshare_ideal`` = min(cores, np)/np — the ceiling the metric
    has on this machine;
  * ``efficiency_vs_achievable`` = raw / ideal — how close the
    framework gets to that ceiling (this is the number comparable to
    the reference's published 90%, which was measured with one GPU
    per rank, i.e. compute actually parallel);
  * a ``fixed_compute`` scenario where the per-step compute is a
    sleep (parallelizable even on one core, like real accelerator
    compute) and only the gradient exchange costs CPU — isolating the
    framework's communication overhead the way a real cluster would.

Run with no arguments to orchestrate everything (spawns the worlds,
writes benchmarks/RESULTS_cpu.json):

    python benchmarks/collective_bench.py [--np 8]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALLREDUCE_SIZES = [4 << 10, 64 << 10, 1 << 20, 4 << 20, 16 << 20]
FUSED_COUNT, FUSED_BYTES = 32, 128 << 10
ALLREDUCE_ITERS = 21
TRAIN_STEPS = 30
FIXED_COMPUTE_S = 0.100  # simulated per-step compute (parallelizable)

VARIANTS = {
    # name -> extra env for the world
    "shm": {},
    "star": {"HOROVOD_TPU_SHM": "0", "HOROVOD_TPU_RING_THRESHOLD": "-1"},
    "ring": {"HOROVOD_TPU_SHM": "0",
             "HOROVOD_TPU_RING_THRESHOLD": "32768"},
}


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _quantiles(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 4], xs[n // 2], xs[(3 * n) // 4]


# ---------------------------------------------------------------------------
# worker halves (run in subprocesses)
# ---------------------------------------------------------------------------

def worker_allreduce(rank: int, size: int) -> None:
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    results = []
    for nbytes in ALLREDUCE_SIZES:
        n = nbytes // 4
        x = np.full((n,), float(rank + 1), np.float32)
        for i in range(3):
            hvd.allreduce(x, average=False, name=f"warm.{nbytes}.{i}")
        hvd.barrier(name=f"bar.{nbytes}")
        times = []
        for i in range(ALLREDUCE_ITERS):
            t0 = time.perf_counter()
            out = hvd.allreduce(x, average=False,
                                name=f"ar.{nbytes}.{i}")
            times.append(time.perf_counter() - t0)
        assert abs(float(out[0]) - sum(range(1, size + 1))) < 1e-4
        p25, med, p75 = _quantiles(times)
        algbw = nbytes / med
        results.append({
            "bytes": nbytes,
            "us_per_op": round(med * 1e6, 1),
            "us_p25": round(p25 * 1e6, 1),
            "us_p75": round(p75 * 1e6, 1),
            "algbw_MBps": round(algbw / 1e6, 2),
            # ring-equivalent bus bandwidth (nccl-tests convention)
            "busbw_MBps": round(algbw * 2 * (size - 1) / size / 1e6, 2),
        })

    # fused batch: FUSED_COUNT tensors submitted together ride one
    # negotiated cycle / fused response
    xs = [np.full((FUSED_BYTES // 4,), float(rank + 1), np.float32)
          for _ in range(FUSED_COUNT)]
    for rep in range(2):
        handles = [hvd.allreduce_async(x, average=False,
                                       name=f"fw.{rep}.{i}")
                   for i, x in enumerate(xs)]
        for h in handles:
            hvd.synchronize(h)
    hvd.barrier(name="bar.fused")
    times = []
    for rep in range(ALLREDUCE_ITERS):
        t0 = time.perf_counter()
        handles = [hvd.allreduce_async(x, average=False,
                                       name=f"f.{rep}.{i}")
                   for i, x in enumerate(xs)]
        for h in handles:
            hvd.synchronize(h)
        times.append(time.perf_counter() - t0)
    total = FUSED_COUNT * FUSED_BYTES
    _, med, _ = _quantiles(times)
    fused = {
        "bytes": total, "tensors": FUSED_COUNT,
        "us_per_batch": round(med * 1e6, 1),
        "algbw_MBps": round(total / med / 1e6, 2),
        "busbw_MBps": round(
            total / med * 2 * (size - 1) / size / 1e6, 2),
    }
    if rank == 0:
        print("RESULT " + json.dumps(
            {"allreduce": results, "fused": fused}), flush=True)
    hvd.shutdown()


def worker_train(rank: int, size: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import horovod_tpu.jax as hvd

    hvd.init()
    rng = np.random.RandomState(42)  # same data shape on every rank
    w_sizes = [(256, 512), (512, 512), (512, 256)]
    params = [jnp.asarray(rng.randn(*s) * 0.01, jnp.float32)
              for s in w_sizes]
    params = hvd.broadcast_parameters(params, root_rank=0)
    tx = optax.sgd(0.01)
    opt_state = tx.init(params)
    x = jnp.asarray(rng.randn(64, 256), jnp.float32)

    @jax.jit
    def loss_grads(params, x):
        def loss_fn(ps):
            h = x
            for w in ps:
                h = jnp.tanh(h @ w)
            return (h ** 2).mean()
        return jax.value_and_grad(loss_fn)(params)

    @jax.jit
    def apply(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def step(params, opt_state):
        loss, grads = loss_grads(params, x)
        # the framework's out-of-jit gradient path: enqueue every leaf,
        # negotiate, fuse, execute, synchronize
        grads = hvd.allreduce_gradients(grads)
        params, opt_state = apply(params, opt_state, grads)
        return params, opt_state, loss

    for _ in range(5):
        params, opt_state, loss = step(params, opt_state)
    float(loss)
    hvd.barrier(name="bar.train")
    times = []
    for _ in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state)
        float(loss)
        times.append(time.perf_counter() - t0)
    _, med, _ = _quantiles(times)
    if rank == 0:
        print("RESULT " + json.dumps(
            {"steps_per_sec": round(1.0 / med, 2)}), flush=True)
    hvd.shutdown()


def worker_fixed_compute(rank: int, size: int) -> None:
    """Per-step compute is a sleep — parallelizable across ranks even on
    one core, like real accelerator compute — so the measured slowdown
    vs np=1 is purely the framework's communication overhead."""
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    grads = [np.full((256, 512), 0.1 * (rank + 1), np.float32),
             np.full((512, 512), 0.2 * (rank + 1), np.float32),
             np.full((512, 256), 0.3 * (rank + 1), np.float32)]

    def step(i):
        time.sleep(FIXED_COMPUTE_S)
        handles = [hvd.allreduce_async(g, average=True,
                                       name=f"fc.{i}.{j}")
                   for j, g in enumerate(grads)]
        for h in handles:
            hvd.synchronize(h)

    for i in range(3):
        step(-1 - i)
    hvd.barrier(name="bar.fc")
    times = []
    for i in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        step(i)
        times.append(time.perf_counter() - t0)
    _, med, _ = _quantiles(times)
    if rank == 0:
        print("RESULT " + json.dumps(
            {"steps_per_sec": round(1.0 / med, 2)}), flush=True)
    hvd.shutdown()


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

def worker_overhead(rank: int, size: int) -> None:
    """Isolate the per-step control-plane cost: a BARRIER is a pure
    negotiate+dispatch round (no payload), and a 4 KiB allreduce adds
    only a trivial payload — their medians are the framework overhead a
    training step pays on top of compute, the quantity that bounds
    pod-scale efficiency (the data-plane bytes ride ICI on real
    hardware and overlap with backward)."""
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    for i in range(5):
        hvd.barrier(name=f"warm.{i}")
    ts_bar = []
    for i in range(ALLREDUCE_ITERS * 2):
        t0 = time.perf_counter()
        hvd.barrier(name=f"ov.bar.{i}")
        ts_bar.append(time.perf_counter() - t0)
    x = np.full((1024,), float(rank + 1), np.float32)
    ts_small = []
    for i in range(ALLREDUCE_ITERS * 2):
        t0 = time.perf_counter()
        out = hvd.allreduce(x, average=False, name=f"ov.ar.{i}")
        ts_small.append(time.perf_counter() - t0)
    assert abs(float(out[0]) - sum(range(1, size + 1))) < 1e-4
    _, bar_med, _ = _quantiles(ts_bar)
    _, small_med, _ = _quantiles(ts_small)
    if rank == 0:
        print("RESULT " + json.dumps({
            "barrier_us": round(bar_med * 1e6, 1),
            "small_allreduce_us": round(small_med * 1e6, 1),
        }), flush=True)
    hvd.shutdown()


ELASTIC_BENCH_STEPS = 400      # total steady allreduce steps
ELASTIC_BENCH_KILL_OP = 150    # victim's SIGKILL lands mid-run


def worker_elastic(rank: int, size: int) -> None:
    """Elastic recovery section: a steady single-tensor loop at ws=N;
    the highest rank is SIGKILLed mid-run by fault injection
    (HOROVOD_FAULT_SPEC, set by the section driver) and the survivors
    re-rendezvous into ws=N-1 and finish. The surviving rank 0 reports
    steady-state us/op BEFORE the kill, the re-rendezvous GAP (the one
    step interval that contains detection + barrier + re-init +
    resync), and us/op AFTER the shrink — the recovery-time budget is
    asserted against 2x the heartbeat timeout by the driver."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import config as hconfig
    from horovod_tpu.common import elastic

    hvd.init()
    launch_rank = hconfig.env_int("HOROVOD_RANK", rank)
    x = np.full(16384, float(rank + 1), np.float32)  # 64 KiB payload
    state = elastic.State(batch=0)
    stamps = []  # (t_after_step, world_size)

    @elastic.run
    def train(state):
        while state.batch < ELASTIC_BENCH_STEPS:
            hvd.allreduce(x, average=False, name="el.bench")
            state.batch += 1
            state.commit()
            stamps.append((time.monotonic(), hvd.size()))

    train(state)
    if launch_rank != 0:
        hvd.shutdown()
        return
    pre, post, gap = [], [], None
    for (t0, ws0), (t1, ws1) in zip(stamps, stamps[1:]):
        dt = t1 - t0
        if ws0 == size and ws1 == size:
            pre.append(dt)
        elif ws0 == size - 1 and ws1 == size - 1:
            post.append(dt)
        else:
            gap = dt  # the transition step: detection + re-rendezvous
    ctx = elastic.context()
    _, pre_med, _ = _quantiles(pre)
    _, post_med, _ = _quantiles(post)
    print("RESULT " + json.dumps({
        "world": size,
        "steps": ELASTIC_BENCH_STEPS,
        "pre_kill_us_per_op": round(pre_med * 1e6, 1),
        "post_shrink_us_per_op": round(post_med * 1e6, 1),
        "rendezvous_gap_ms": round((gap or 0.0) * 1e3, 1),
        "barrier_ms": round(ctx.last_rendezvous_s * 1e3, 1),
        "generation": ctx.membership.generation,
    }), flush=True)
    hvd.shutdown()


def _elastic_bench_section(np_: int) -> dict:
    """`--elastic`: steady us/op before the kill, the re-rendezvous
    gap, and us/op after the shrink, with the recovery time asserted
    under 2x the heartbeat timeout."""
    hb_timeout = 2.0
    r = _run_world(
        "elastic", np_, timeout=300.0,
        extra_env={
            "HOROVOD_ELASTIC": "1",
            "HOROVOD_ELASTIC_WINDOW": "10",
            "HOROVOD_HEARTBEAT_INTERVAL": "0.2",
            "HOROVOD_HEARTBEAT_TIMEOUT": str(hb_timeout),
            "HOROVOD_TPU_SHM": "0",
            "HOROVOD_FAULT_SPEC":
                f"rank={np_ - 1}:kill:op={ELASTIC_BENCH_KILL_OP}",
        },
        allow_rc={np_ - 1: -9})
    r["heartbeat_timeout_s"] = hb_timeout
    r["recovery_budget_ms"] = round(2 * hb_timeout * 1e3, 1)
    r["recovery_within_budget"] = \
        r["rendezvous_gap_ms"] < 2 * hb_timeout * 1e3
    assert r["recovery_within_budget"], (
        f"re-rendezvous gap {r['rendezvous_gap_ms']} ms exceeded the "
        f"2x-heartbeat budget {r['recovery_budget_ms']} ms")
    return r


SELFOP_SYNC_KEYS = 1024        # model-shaped state: many tensors...
SELFOP_SYNC_KEY_ELEMS = 16384  # ...of 64 KiB f32 each = 64 MiB total
SELFOP_SYNC_ITERS = 3


def worker_selfop_sync(rank: int, size: int) -> None:
    """Rejoin-sync section: time ``State.sync()`` over a 1024-tensor,
    64 MiB model-shaped state — exactly what a rejoiner or a
    post-resize world pays before its first step. Run in pairs by the
    driver: the chunked tree-pipelined fast path (HOROVOD_SELFOP_SYNC=1,
    common/selfop.py) vs the legacy one-shot-per-key negotiated
    broadcast (=0). The fast leg also reports the
    hvd_data_copies_total delta across its syncs — the zero-copy
    claim: no sync byte ever pays a Python bytes-object copy."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import config as hconfig
    from horovod_tpu.common import elastic

    hvd.init()
    vals = {}
    for i in range(SELFOP_SYNC_KEYS):
        if rank == 0:
            vals[f"p{i:03d}"] = np.full(SELFOP_SYNC_KEY_ELEMS,
                                        float(i + 1), np.float32)
        else:
            vals[f"p{i:03d}"] = np.zeros(SELFOP_SYNC_KEY_ELEMS,
                                         np.float32)
    state = elastic.State(batch=0, **vals)

    def copies():
        return hvd.metrics()["local"].get(
            "hvd_data_copies_total", {}).get("v", 0)

    hvd.barrier(name="ss.warm")
    c0 = copies()
    times = []
    for _ in range(SELFOP_SYNC_ITERS):
        hvd.barrier(name="ss.bar")
        t0 = time.perf_counter()
        state.sync()
        times.append(time.perf_counter() - t0)
    c1 = copies()
    # every member now holds rank 0's values bit-for-bit
    for i in range(SELFOP_SYNC_KEYS):
        v = state._values[f"p{i:03d}"]
        assert float(v[0]) == float(i + 1) and float(v[-1]) == \
            float(i + 1), (i, v[0], v[-1])
    _, med, _ = _quantiles(times)
    if rank == 0:
        ctx = elastic.context()
        fast_on = hconfig.env_bool("HOROVOD_SELFOP_SYNC", True)
        print("RESULT " + json.dumps({
            "world": size,
            "state_mib": round(SELFOP_SYNC_KEYS * SELFOP_SYNC_KEY_ELEMS
                               * 4 / 2**20, 1),
            "keys": SELFOP_SYNC_KEYS,
            "sync_ms": round(med * 1e3, 1),
            "fast_path": bool(fast_on),
            "fast_syncs": ctx.syncs if ctx is not None else 0,
            "data_copies_delta": int(c1 - c0),
        }), flush=True)
    hvd.shutdown()


def _selfop_bench_section(np_: int) -> dict:
    """`--selfop`: the rejoin-sync A/B — chunked tree-pipelined
    fast path vs the legacy per-key negotiated broadcast, same
    64 MiB state, socket plane (the multi-host shape where rejoin
    cost actually matters)."""
    base = {
        "HOROVOD_ELASTIC": "1",
        "HOROVOD_ELASTIC_WINDOW": "10",
        "HOROVOD_TPU_SHM": "0",
        "HOROVOD_TPU_METRICS": "1",
        # The legacy leg is 1024 back-to-back broadcasts — enough
        # telemetry for the supervision policy to demote whichever
        # rank habitually arrives last. Benching, not training:
        # park the demotion trigger out of reach.
        "HOROVOD_SELFOP_DEMOTE_WINDOW": "1000000000",
    }
    fast = _run_world(
        "selfop_sync", np_, timeout=300.0,
        extra_env=dict(base, HOROVOD_SELFOP_SYNC="1"))
    legacy = _run_world(
        "selfop_sync", np_, timeout=600.0,
        extra_env=dict(base, HOROVOD_SELFOP_SYNC="0"))
    assert fast["fast_syncs"] >= SELFOP_SYNC_ITERS, fast
    assert legacy["fast_syncs"] == 0, legacy
    speedup = round(legacy["sync_ms"] / max(fast["sync_ms"], 1e-9), 2)
    return {
        "world": np_,
        "state_mib": fast["state_mib"],
        "keys": fast["keys"],
        "fast_sync_ms": fast["sync_ms"],
        "legacy_sync_ms": legacy["sync_ms"],
        "speedup": speedup,
        "meets_3x": speedup >= 3.0,
        "fast_data_copies_delta": fast["data_copies_delta"],
        "zero_copy_clean": fast["data_copies_delta"] == 0,
    }


CACHE_BENCH_TENSORS = 64       # 4 KiB grads per steady-state step
CACHE_BENCH_STEPS = 100
CACHE_BENCH_GAP_S = 0.005      # simulated per-step compute (backward)


def worker_cache(rank: int, size: int) -> None:
    """Negotiation-overhead section: a steady-state training-shaped
    loop — the SAME 64 x 4 KiB gradient bucket every step (one
    grouped_allreduce_async, the way a DDP-style integration submits a
    gradient bucket), with a short think-time between steps standing
    in for the backward pass. This is exactly the traffic the
    bit-vector response cache (HOROVOD_CACHE_*) turns into one fused
    bitmask+data round per step. Run in on/off pairs by the
    orchestrator (cache on / HOROVOD_CACHE_ENABLED=0): us_per_op is a
    4 KiB allreduce's share of the median step latency (submit ->
    drained, think-time excluded). Reports the hit-rate and
    cached/fused-cycle counters measured AFTER warmup (acceptance
    bar: >= 99% hits over the 100-step loop)."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import basics as _b

    hvd.init()
    n = (4 << 10) // 8
    xs = [np.full(n, float(rank + 1) * (i + 1), np.float64)
          for i in range(CACHE_BENCH_TENSORS)]
    ssum = sum(range(1, size + 1))

    def step():
        hs = hvd.grouped_allreduce_async(xs, average=False, name="cb")
        for h in hs:
            hvd.synchronize(h)

    for _ in range(5):
        step()
        time.sleep(CACHE_BENCH_GAP_S)
    hvd.barrier(name="cb.bar")
    rt = _b.runtime()
    s0 = rt.negotiation_cache_stats()
    c0 = rt._cycle_count
    m0 = hvd.metrics()["local"]
    times = []
    for _ in range(CACHE_BENCH_STEPS):
        t0 = time.perf_counter()
        step()
        times.append(time.perf_counter() - t0)
        time.sleep(CACHE_BENCH_GAP_S)
    s1 = rt.negotiation_cache_stats()
    c1 = rt._cycle_count
    m1 = hvd.metrics()["local"]
    # correctness spot check of the steady-state values
    out = hvd.grouped_allreduce(xs, average=False, name="cb")
    for i in range(CACHE_BENCH_TENSORS):
        assert abs(float(np.asarray(out[i])[0])
                   - ssum * (i + 1)) < 1e-6
    _, med, _ = _quantiles(times)
    report = {
        "tensors_per_step": CACHE_BENCH_TENSORS,
        "bytes_per_tensor": 4 << 10,
        "steps": CACHE_BENCH_STEPS,
        "us_per_step": round(med * 1e6, 1),
        "us_per_op": round(med * 1e6 / CACHE_BENCH_TENSORS, 1),
        # the full per-step series, for paired estimators: a
        # simultaneous A/B pair's step k on each side shares the
        # same wall-clock throttle state, so index-paired ratios
        # cancel the common-mode noise that swamps sub-percent
        # effects
        "step_times_us": [round(t * 1e6, 1) for t in times],
        "cycles_per_step": round((c1 - c0) / CACHE_BENCH_STEPS, 2),
        "cache_enabled": bool(s1.get("enabled")),
    }
    if m1:  # metrics armed: steady-bucket copies (zero-copy contract)
        report["data_copies"] = int(
            m1.get("hvd_data_copies_total", {"v": 0.0})["v"]
            - m0.get("hvd_data_copies_total", {"v": 0.0})["v"])
    if s1.get("enabled"):
        d_hits = s1["hits"] - s0["hits"]
        d_misses = s1["misses"] - s0["misses"]
        report["hit_rate"] = round(
            d_hits / max(1, d_hits + d_misses), 4)
        report["cached_cycles"] = (s1["cached_cycles"]
                                   - s0["cached_cycles"])
        report["fused_spec_cycles"] = (s1["spec_cycles"]
                                       - s0["spec_cycles"])
        report["native_steady_cycles"] = (
            s1.get("native_steady_cycles", 0)
            - s0.get("native_steady_cycles", 0))
    if rank == 0:
        print("RESULT " + json.dumps(report), flush=True)
    hvd.shutdown()


def _cache_bench_section(np_: int) -> dict:
    """A/B the negotiation fast path at world_size=np_ on the CPU
    socket backend (shm/ring off so the data plane is socket in both
    runs and only the control protocol differs). This host's
    scheduler throttles in multi-second bursts, so sequential on/off
    runs are drift-dominated; instead run each on/off pair
    SIMULTANEOUSLY — both worlds experience the identical machine at
    every instant, which makes the per-pair ratio stable — and report
    the median of the per-pair ratios."""
    import threading
    cache_env = {"HOROVOD_TPU_SHM": "0",
                 "HOROVOD_TPU_RING_THRESHOLD": "-1"}
    off_env = dict(cache_env, HOROVOD_CACHE_ENABLED="0")

    ons, offs, ratios = [], [], []
    for rep in range(3):
        pair = {}

        def _go(key, env):
            pair[key] = _run_world("cache", np_, timeout=600.0,
                                   extra_env=env)

        ta = threading.Thread(target=_go, args=("on", cache_env))
        tb = threading.Thread(target=_go, args=("off", off_env))
        ta.start()
        tb.start()
        ta.join()
        tb.join()
        ons.append(pair["on"])
        offs.append(pair["off"])
        ratios.append(pair["off"]["us_per_op"]
                      / pair["on"]["us_per_op"])
    ons.sort(key=lambda d: d["us_per_op"])
    offs.sort(key=lambda d: d["us_per_op"])
    ratios.sort()
    return {"world_size": np_,
            "cache_on": ons[len(ons) // 2],
            "cache_off": offs[len(offs) // 2],
            "pair_ratios": [round(r, 2) for r in ratios],
            "speedup": round(ratios[len(ratios) // 2], 2)}


def _zero_copy_bench_section(np_: int) -> dict:
    """Zero-copy native data plane A/B on the PR 3 steady bucket:
    both legs run the full fast path (cache + fused speculative
    cycle, socket star); the off leg sets HOROVOD_TPU_ZERO_COPY=0,
    which restores the PR 3 byte-copy paths (Python serialization,
    bytes recv, bytearray copies) while keeping the wire format
    identical.

    TWO protocols, both recorded:

    * SIMULTANEOUS pairs (the cache section's protocol — immune to
      this host's multi-second throttle bursts). Caveat it inherits
      on a host whose core count is below 2 x world_size: the two
      worlds serialize through one run queue, so the fast world's
      measured step absorbs the slow world's CPU share and the pair
      ratio is CAPPED near (1+k)/k regardless of the true gap (~1.5x
      observed ceiling on the 1-core reference box even with the fast
      leg's data plane made nearly free).
    * ISOLATED alternating legs (on/off/on/off...): each world owns
      the machine; adjacent runs see similar throttle states, and the
      median of adjacent ratios is the undistorted data-plane
      speedup. This is the headline number on hosts where the pair
      cannot genuinely run side by side."""
    import threading
    base_env = {"HOROVOD_TPU_SHM": "0",
                "HOROVOD_TPU_RING_THRESHOLD": "-1"}
    off_env = dict(base_env, HOROVOD_TPU_ZERO_COPY="0")

    ons, offs, ratios = [], [], []
    for rep in range(3):
        pair = {}

        def _go(key, env):
            pair[key] = _run_world("cache", np_, timeout=600.0,
                                   extra_env=env)

        ta = threading.Thread(target=_go, args=("on", base_env))
        tb = threading.Thread(target=_go, args=("off", off_env))
        ta.start()
        tb.start()
        ta.join()
        tb.join()
        ons.append(pair["on"])
        offs.append(pair["off"])
        ratios.append(pair["off"]["us_per_op"]
                      / pair["on"]["us_per_op"])
    iso_ons, iso_offs, iso_ratios = [], [], []
    for rep in range(3):
        a = _run_world("cache", np_, timeout=600.0,
                       extra_env=base_env)
        b = _run_world("cache", np_, timeout=600.0,
                       extra_env=off_env)
        iso_ons.append(a)
        iso_offs.append(b)
        iso_ratios.append(b["us_per_op"] / a["us_per_op"])
    ons.sort(key=lambda d: d["us_per_op"])
    offs.sort(key=lambda d: d["us_per_op"])
    ratios.sort()
    iso_ons.sort(key=lambda d: d["us_per_op"])
    iso_offs.sort(key=lambda d: d["us_per_op"])
    iso_ratios.sort()
    return {"world_size": np_,
            "cores": os.cpu_count(),
            "zero_copy_on": ons[len(ons) // 2],
            "zero_copy_off": offs[len(offs) // 2],
            "pair_ratios": [round(r, 2) for r in ratios],
            "speedup": round(ratios[len(ratios) // 2], 2),
            "isolated_on": iso_ons[len(iso_ons) // 2],
            "isolated_off": iso_offs[len(iso_offs) // 2],
            "isolated_ratios": [round(r, 2) for r in iso_ratios],
            "isolated_speedup": round(
                iso_ratios[len(iso_ratios) // 2], 2)}


OVERLAP_BENCH_TENSORS = 16
OVERLAP_BENCH_BUCKETS = 4
OVERLAP_BENCH_STEPS = 50
# 256 KiB/tensor -> 4 MiB/step: payload work (HMAC + memcpy) must
# dominate the fixed per-round protocol cost, or bucketing's extra
# rounds eat the overlap on a 1-core host (measured crossover ~64 KiB).
OVERLAP_BENCH_ELEMS = 65536


def worker_multitenant(rank: int, size: int) -> None:
    """Multi-tenant section (docs/multitenancy.md): one or two
    tenants spanning the whole fleet run an identical per-tenant
    workload from separate threads. Two program shapes:

    * ``paced`` (HVD_BENCH_THINK_MS) — a training-shaped loop: one
      64 KiB allreduce then a think-time sleep (compute stand-in;
      releases the GIL like device compute). The shared-fleet leg's
      per-tenant throughput vs the isolated leg measures co-tenancy
      overhead.
    * ``saturated`` (HVD_BENCH_SATURATE=1) — a 4-deep async pipeline
      with no think time: both lanes stay backlogged, so the
      QoS-weighted interleave is the binding constraint and the
      cycle share at the first tenant's completion measures it.

    Reports per-tenant elapsed/ops_per_s plus lane stats (cycles,
    deferrals) and — with two tenants — the second tenant's completed
    cycles at the moment the first finishes."""
    import threading
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    nten = int(os.environ.get("HVD_BENCH_TENANTS", "2"))
    weights = [float(w) for w in
               os.environ.get("HVD_BENCH_WEIGHTS", "1,1").split(",")]
    think_s = float(os.environ.get("HVD_BENCH_THINK_MS", "5")) / 1e3
    saturate = os.environ.get("HVD_BENCH_SATURATE") == "1"
    steps = int(os.environ.get("HVD_BENCH_STEPS", "150"))
    names = ["jobA", "jobB"][:nten]
    tenants = [hvd.create_tenant(n, list(range(size)), weight=w)
               for n, w in zip(names, weights)]
    x = np.full(16384, float(rank + 1), np.float32)  # 64 KiB
    ssum = float(sum(range(1, size + 1)))
    out: dict = {}

    def run(t, key, first):
        t0 = time.monotonic()
        if saturate:
            depth, pend = 4, []
            for i in range(steps):
                pend.append(t.allreduce_async(
                    x, average=False, name=f"{key}.g{i % depth}"))
                if len(pend) >= depth:
                    assert float(np.asarray(
                        t.synchronize(pend.pop(0)))[0]) == ssum
            while pend:
                t.synchronize(pend.pop(0))
        else:
            for _ in range(steps):
                r = t.allreduce(x, average=False, name=f"{key}.g")
                assert float(np.asarray(r)[0]) == ssum
                if think_s:
                    time.sleep(think_s)
        out[key] = {"elapsed_s": time.monotonic() - t0}
        if first and len(tenants) > 1:
            out["peer_cycles_at_first_done"] = \
                tenants[1].lane_stats()["cycles"]

    threads = [threading.Thread(target=run, args=(t, k, i == 0))
               for i, (t, k) in enumerate(zip(tenants, names))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    result = {"size": size, "steps": steps, "tenants": {}}
    for t, key in zip(tenants, names):
        stats = t.lane_stats()
        result["tenants"][key] = {
            "elapsed_s": round(out[key]["elapsed_s"], 4),
            "ops_per_s": round(steps / out[key]["elapsed_s"], 2),
            "cycles": stats["cycles"],
            "deferrals": stats["deferrals"],
            "weight": stats["weight"],
        }
    if "peer_cycles_at_first_done" in out:
        result["peer_cycles_at_first_done"] = \
            out["peer_cycles_at_first_done"]
        result["first_cycles"] = \
            result["tenants"][names[0]]["cycles"]
    for t in tenants:
        t.shutdown()
    if rank == 0:
        print("RESULT " + json.dumps(result), flush=True)
    hvd.shutdown()


def _multitenant_bench_section(np_: int) -> dict:
    """Shared-fleet throughput (isolated-leg protocol, alternating
    reps so adjacent runs share this throttling host's phase) and the
    priority-weight cycle-share shift (saturated legs, equal weights
    vs 3:1)."""
    reps = 2
    iso_rates, shared = [], []
    for _ in range(reps):
        iso = _run_world("multitenant", np_, timeout=300.0,
                         extra_env={"HVD_BENCH_TENANTS": "1"})
        iso_rates.append(iso["tenants"]["jobA"]["ops_per_s"])
        sh = _run_world("multitenant", np_, timeout=300.0,
                        extra_env={"HVD_BENCH_TENANTS": "2"})
        shared.append(sh)
    iso_rate = _quantiles(iso_rates)[1]
    ratios_a = [s["tenants"]["jobA"]["ops_per_s"] / iso_rate
                for s in shared]
    ratios_b = [s["tenants"]["jobB"]["ops_per_s"] / iso_rate
                for s in shared]
    ratio_a = _quantiles(ratios_a)[1]
    ratio_b = _quantiles(ratios_b)[1]

    def _share(weights: str) -> dict:
        r = _run_world("multitenant", np_, timeout=300.0,
                       extra_env={"HVD_BENCH_TENANTS": "2",
                                  "HVD_BENCH_WEIGHTS": weights,
                                  "HVD_BENCH_SATURATE": "1",
                                  "HVD_BENCH_STEPS": "400"})
        peer = max(1, r["peer_cycles_at_first_done"])
        return {"first_cycles": r["first_cycles"],
                "peer_cycles_at_first_done": peer,
                "share": round(r["first_cycles"] / peer, 3),
                "light_deferrals":
                    r["tenants"]["jobB"]["deferrals"]}

    equal = _share("1,1")
    skewed = _share("3,1")
    shift = round(skewed["share"] / max(0.01, equal["share"]), 3)
    return {
        "np": np_,
        "protocol": "isolated-leg alternating reps; 64KiB f32 op + "
                    "5ms think per step (paced legs); saturated "
                    "4-deep async pipeline for the share legs",
        "isolated_ops_per_s": iso_rate,
        "shared_ops_per_s": {
            "jobA": _quantiles(
                [s["tenants"]["jobA"]["ops_per_s"]
                 for s in shared])[1],
            "jobB": _quantiles(
                [s["tenants"]["jobB"]["ops_per_s"]
                 for s in shared])[1]},
        "shared_vs_isolated": {"jobA": round(ratio_a, 3),
                               "jobB": round(ratio_b, 3)},
        "min_tenant_fraction": round(min(ratio_a, ratio_b), 3),
        "meets_60pct": bool(min(ratio_a, ratio_b) >= 0.6),
        "cycle_share_equal_weights": equal,
        "cycle_share_3to1": skewed,
        "share_shift_3to1_vs_equal": shift,
        "weights_shift_share": bool(shift > 1.15
                                    and skewed["light_deferrals"] > 0),
    }


def worker_overlap(rank: int, size: int) -> None:
    """Overlap-tier section: a steady training-shaped loop whose
    backward pass is modeled by injected compute (sleep — it releases
    the GIL exactly like device compute does) producing gradient
    BUCKETS progressively. Two program shapes, selected by
    OVERLAP_BENCH_MODE:

    * ``bucketed`` — the overlap tier's contract: each bucket is
      submitted the moment its compute slice ends (ready-order
      dispatch), so its cycle negotiates + reduces on the in-flight
      runner while later slices still compute. Step time tends to
      compute + one bucket's wire time.
    * ``flat`` — today's synchronous pattern: the single grouped
      submission needs the WHOLE gradient set, so it happens after
      all compute and the full wire time lands on the critical path.

    Identical tensors, bytes and injected compute either way
    (OVERLAP_BENCH_COMPUTE_US total per step, calibrated by the
    orchestrator to the measured wire time — the regime the tier
    targets). Reports median step time plus the engagement counters
    (overlap cycles, mean hvd_overlap_fraction, data copies, wire
    bytes saved)."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import basics as _b

    hvd.init()
    mode = os.environ.get("OVERLAP_BENCH_MODE", "bucketed")
    compute_us = int(os.environ.get("OVERLAP_BENCH_COMPUTE_US", "0"))
    k = OVERLAP_BENCH_BUCKETS
    per = OVERLAP_BENCH_TENSORS // k
    xs = [np.full(OVERLAP_BENCH_ELEMS, float(rank + 1) * (i + 1),
                  np.float32)
          for i in range(OVERLAP_BENCH_TENSORS)]
    buckets = [xs[i * per:(i + 1) * per] for i in range(k)]
    slice_s = compute_us / 1e6 / k
    ssum = sum(range(1, size + 1))

    def step():
        handles = []
        if mode == "bucketed":
            for i, bucket in enumerate(buckets):
                if slice_s:
                    time.sleep(slice_s)  # bucket i's backward slice
                handles.extend(hvd.grouped_allreduce_async(
                    bucket, average=False, name=f"ov{i}"))
        else:
            for _ in range(k):
                if slice_s:
                    time.sleep(slice_s)  # same producer timeline
            handles.extend(hvd.grouped_allreduce_async(
                xs, average=False, name="ovf"))
        for h in handles:
            hvd.synchronize(h)

    for _ in range(8):
        step()
    hvd.barrier(name="ovb.bar")
    rt = _b.runtime()
    s0 = rt.negotiation_cache_stats()
    m0 = hvd.metrics()["local"]
    times = []
    for _ in range(OVERLAP_BENCH_STEPS):
        t0 = time.perf_counter()
        step()
        times.append(time.perf_counter() - t0)
    s1 = rt.negotiation_cache_stats()
    m1 = hvd.metrics()["local"]
    # correctness spot check of the steady-state values
    out = hvd.grouped_allreduce(xs, average=False, name="ovchk")
    for i in range(OVERLAP_BENCH_TENSORS):
        assert abs(float(np.asarray(out[i])[0])
                   - ssum * (i + 1)) < 1e-3

    def _delta(name):
        return (m1.get(name, {"v": 0.0})["v"]
                - m0.get(name, {"v": 0.0})["v"])

    frac = m1.get("hvd_overlap_fraction")
    f0 = m0.get("hvd_overlap_fraction")
    mean_frac = None
    if frac and frac.get("count", 0) > (f0 or {}).get("count", 0):
        dc = frac["count"] - (f0 or {"count": 0, "sum": 0.0})["count"]
        ds = frac["sum"] - (f0 or {"count": 0, "sum": 0.0})["sum"]
        mean_frac = round(ds / max(1, dc), 3)
    _, med, _ = _quantiles(times)
    report = {
        "mode": mode,
        "tensors_per_step": OVERLAP_BENCH_TENSORS,
        "buckets": k if mode == "bucketed" else 1,
        "bytes_per_tensor": OVERLAP_BENCH_ELEMS * 4,
        "compute_us_per_step": compute_us,
        "steps": OVERLAP_BENCH_STEPS,
        "us_per_step": round(med * 1e6, 1),
        "overlap_cycles": (s1.get("overlap_cycles", 0)
                           - s0.get("overlap_cycles", 0)),
        "native_steady_cycles": (s1.get("native_steady_cycles", 0)
                                 - s0.get("native_steady_cycles", 0)),
        "spec_cycles": s1["spec_cycles"] - s0["spec_cycles"],
        "overlap_fraction_mean": mean_frac,
        "data_copies": int(_delta("hvd_data_copies_total")),
        "wire_bytes_saved": int(_delta("hvd_wire_bytes_saved_total")),
    }
    if rank == 0:
        print("RESULT " + json.dumps(report), flush=True)
    hvd.shutdown()


def _overlap_bench_section(np_: int) -> dict:
    """`--overlap`: A/B the overlap tier against the synchronous
    steady path with injected per-step compute CALIBRATED to the
    measured wire time (the acceptance regime: compute comparable to
    comm). Protocols as for --steady-only: isolated alternating legs
    (the honest number on a host that cannot truly run two worlds
    side by side) plus simultaneous pairs, and one compressed leg
    proving compression + chunked transfer stay engaged per bucket."""
    import threading
    on_env = {"HOROVOD_TPU_SHM": "0",
              "HOROVOD_TPU_RING_THRESHOLD": "-1",
              "HOROVOD_TPU_METRICS": "1",
              "HOROVOD_OVERLAP_INFLIGHT": "2",
              "OVERLAP_BENCH_MODE": "bucketed"}
    off_env = dict(on_env, HOROVOD_OVERLAP_INFLIGHT="0",
                   OVERLAP_BENCH_MODE="flat")

    # Calibrate: the flat leg's step with zero injected compute IS
    # the steady wire+protocol time; inject that much compute.
    probe = _run_world("overlap", np_, timeout=600.0,
                       extra_env=dict(off_env,
                                      OVERLAP_BENCH_COMPUTE_US="0"))
    compute_us = max(500, int(probe["us_per_step"]))
    on_env["OVERLAP_BENCH_COMPUTE_US"] = str(compute_us)
    off_env["OVERLAP_BENCH_COMPUTE_US"] = str(compute_us)

    iso_ons, iso_offs, iso_ratios = [], [], []
    for rep in range(3):
        a = _run_world("overlap", np_, timeout=600.0, extra_env=on_env)
        b = _run_world("overlap", np_, timeout=600.0,
                       extra_env=off_env)
        iso_ons.append(a)
        iso_offs.append(b)
        iso_ratios.append(b["us_per_step"] / a["us_per_step"])
    ons, offs, ratios = [], [], []
    for rep in range(2):
        pair = {}

        def _go(key, env):
            pair[key] = _run_world("overlap", np_, timeout=600.0,
                                   extra_env=env)

        ta = threading.Thread(target=_go, args=("on", on_env))
        tb = threading.Thread(target=_go, args=("off", off_env))
        ta.start()
        tb.start()
        ta.join()
        tb.join()
        ons.append(pair["on"])
        offs.append(pair["off"])
        ratios.append(pair["off"]["us_per_step"]
                      / pair["on"]["us_per_step"])
    comp = _run_world(
        "overlap", np_, timeout=600.0,
        extra_env=dict(on_env, HOROVOD_COMPRESSION="bf16",
                       HOROVOD_OVERLAP_CHUNK_BYTES="4096"))
    iso_ons.sort(key=lambda d: d["us_per_step"])
    iso_offs.sort(key=lambda d: d["us_per_step"])
    iso_ratios.sort()
    ratios.sort()
    med_on = iso_ons[len(iso_ons) // 2]
    sec = {"world_size": np_,
           "cores": os.cpu_count(),
           "compute_us_per_step": compute_us,
           "wire_probe_us_per_step": probe["us_per_step"],
           "overlap_on": med_on,
           "overlap_off": iso_offs[len(iso_offs) // 2],
           "isolated_ratios": [round(r, 2) for r in iso_ratios],
           "isolated_speedup": round(
               iso_ratios[len(iso_ratios) // 2], 2),
           "pair_ratios": [round(r, 2) for r in ratios],
           "pair_speedup": round(
               sorted(ratios)[len(ratios) // 2], 2) if ratios else None,
           "compressed_on": comp,
           "overlap_fraction": med_on.get("overlap_fraction_mean"),
           "zero_copies": med_on.get("data_copies") == 0,
           "meets_1_3x": None,
           "meets_fraction_50pct": None}
    sec["meets_1_3x"] = sec["isolated_speedup"] >= 1.3
    f = sec["overlap_fraction"]
    sec["meets_fraction_50pct"] = (f is not None and f >= 0.5)
    return sec


def _metrics_bench_section(np_: int) -> dict:
    """Metrics-plane overhead A/B on the PR 3 steady bucket (the
    worker_cache loop: 64 x 4 KiB grouped allreduce per step, cache
    on): HOROVOD_TPU_METRICS off (the default — this leg must hold
    the recorded negotiation_cache.cache_on baseline within the <2%
    acceptance bar, since the disabled path installs only no-op
    hooks) vs on (pricing the armed counters/histograms + the
    per-interval world fold). Same simultaneous-pair protocol as the
    cache section: this host throttles in multi-second bursts, so
    only per-pair ratios are stable."""
    import threading
    base_env = {"HOROVOD_TPU_SHM": "0",
                "HOROVOD_TPU_RING_THRESHOLD": "-1"}
    on_env = dict(base_env, HOROVOD_TPU_METRICS="1",
                  HOROVOD_TPU_METRICS_INTERVAL="1")

    offs, ons, ratios = [], [], []
    for rep in range(3):
        pair = {}

        def _go(key, env):
            pair[key] = _run_world("cache", np_, timeout=600.0,
                                   extra_env=env)

        ta = threading.Thread(target=_go, args=("off", base_env))
        tb = threading.Thread(target=_go, args=("on", on_env))
        ta.start()
        tb.start()
        ta.join()
        tb.join()
        offs.append(pair["off"])
        ons.append(pair["on"])
        ratios.append(pair["on"]["us_per_op"]
                      / pair["off"]["us_per_op"])
    offs.sort(key=lambda d: d["us_per_op"])
    ons.sort(key=lambda d: d["us_per_op"])
    ratios.sort()
    med_ratio = ratios[len(ratios) // 2]
    return {"world_size": np_,
            "metrics_off": offs[len(offs) // 2],
            "metrics_on": ons[len(ons) // 2],
            "pair_overhead_pct": [round((r - 1) * 100, 2)
                                  for r in ratios],
            "enabled_overhead_pct": round((med_ratio - 1) * 100, 2)}


AUTOTUNE_VALUE_TENSORS = 24
AUTOTUNE_VALUE_BYTES = 32 << 10
AUTOTUNE_VALUE_STEPS = 40


COMP_BENCH_STEPS = 30
COMP_BENCH_GAP_S = 0.002


def worker_compression(rank: int, size: int) -> None:
    """Compression/algorithm grid leg (ISSUE 9): a steady
    single-tensor allreduce loop at the bucket size in
    HVD_BENCH_BYTES, with wire dtype and algorithm selected by the
    section driver through the production knobs (HOROVOD_COMPRESSION,
    HOROVOD_TWO_LEVEL, HOROVOD_TPU_RING_THRESHOLD, HOROVOD_TPU_SHM) —
    the grid measures exactly what an operator would deploy.
    ``us_per_op`` is the median steady step latency; values are
    bf16-exact small integers so every wire dtype is spot-checkable."""
    import numpy as np
    import horovod_tpu as hvd

    nbytes = int(os.environ.get("HVD_BENCH_BYTES", str(1 << 20)))
    steps = int(os.environ.get("HVD_BENCH_STEPS",
                               str(COMP_BENCH_STEPS)))
    hvd.init()
    n = max(1, nbytes // 4)
    x = np.full(n, float(rank + 1), np.float32)
    ssum = float(sum(range(1, size + 1)))

    out = None
    for _ in range(5):
        out = hvd.allreduce(x, average=False, name="cg")
        time.sleep(COMP_BENCH_GAP_S)
    assert abs(float(np.asarray(out)[0]) - ssum) < 1e-3
    hvd.barrier(name="cg.bar")
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        hvd.allreduce(x, average=False, name="cg")
        times.append(time.perf_counter() - t0)
        time.sleep(COMP_BENCH_GAP_S)
    out = hvd.allreduce(x, average=False, name="cg")
    assert abs(float(np.asarray(out)[0]) - ssum) < 1e-3
    _, med, _ = _quantiles(times)
    report = {
        "bytes": nbytes,
        "steps": steps,
        "us_per_op": round(med * 1e6, 1),
        "compression": os.environ.get("HOROVOD_COMPRESSION", "none"),
    }
    if rank == 0:
        print("RESULT " + json.dumps(report), flush=True)
    hvd.shutdown()


def worker_compression_autotune(rank: int, size: int) -> None:
    """Autotuner-convergence leg: the same steady loop under
    HOROVOD_AUTOTUNE=1 — the per-bucket grid phase sweeps
    (algorithm x wire dtype) live, the BO phase settles
    threshold x cycle, and the post-convergence median latency is
    what the section compares against the best hand-picked grid
    point (acceptance: >= 90% of its throughput)."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import basics as _b
    from horovod_tpu.common import wire_dtype as _wd
    from horovod_tpu.common.parameter_manager import bucket_of

    nbytes = int(os.environ.get("HVD_BENCH_BYTES", str(1 << 20)))
    hvd.init()
    rt = _b.runtime()
    pm = rt.parameter_manager
    assert pm is not None
    n = max(1, nbytes // 4)
    x = np.full(n, float(rank + 1), np.float32)
    converged = False
    for i in range(6000):
        hvd.allreduce(x, average=False, name="ca")
        if i % 5 != 4:
            # Back-to-back ops keep the tuner's score windows DENSE
            # (an op-starved window scores noise); the world-consistent
            # convergence probe only needs to run every few steps.
            continue
        flag = 0.0 if rank != 0 else (0.0 if pm.tuning else 1.0)
        done = hvd.broadcast(np.asarray([flag]), root_rank=0,
                             name=f"ca.done/{i}")
        if float(done[0]) == 1.0:
            converged = True
            break
    hvd.barrier(name="ca.bar")
    times = []
    for _ in range(COMP_BENCH_STEPS):
        t0 = time.perf_counter()
        hvd.allreduce(x, average=False, name="ca")
        times.append(time.perf_counter() - t0)
        time.sleep(COMP_BENCH_GAP_S)
    _, med, _ = _quantiles(times)
    report = {"converged": converged,
              "us_per_op": round(med * 1e6, 1),
              "ops_to_converge": i}
    if rank == 0:
        alg, cap = pm.bucket_plan()[bucket_of(nbytes)]
        report["tuned"] = {
            "algorithm": _wd.ALG_NAMES[alg],
            "wire": "-" if cap is None else _wd.WIRE_NAMES[cap]}
        print("RESULT " + json.dumps(report), flush=True)
    hvd.shutdown()


def _compression_bench_section(np_: int) -> dict:
    """The ISSUE 9 acceptance grid at world_size=np_ on a fake
    multi-host topology (np_//2 hosts x 2 ranks): (algorithm x wire
    dtype x size bucket) medians with ISOLATED legs (3 reps on the
    headline >= 1 MiB bucket), a SIMULTANEOUS star none/bf16 pair
    (the throttle-immune protocol), and the autotuner-convergence
    run. Records:

    * ``bf16_star_speedup`` — median of ADJACENT isolated star
      none/bf16 leg ratios on the >= 1 MiB bucket (acceptance:
      >= 1.5x; the simultaneous pairs are recorded alongside);
    * ``twolevel_vs_best_flat_none`` / ``_bf16`` — best flat
      (star/ring) latency over two-level at the SAME wire dtype;
      the pass bit gates on the NONE ratio (the algorithm
      comparison, acceptance > 1.0) — see the loopback caveat in
      ``twolevel_note`` for why the bf16 column can invert on a
      one-host CI box;
    * ``autotune.frac_of_best`` — throughput fraction of the best
      grid combo (re-measured adjacent in time) the tuned config
      reaches (acceptance: >= 0.9)."""
    import threading

    def hosts(rank: int) -> dict:
        return {"HOROVOD_HOSTNAME": f"bhost{rank // 2}"}

    algs = {
        "star": {"HOROVOD_TPU_SHM": "0",
                 "HOROVOD_TPU_RING_THRESHOLD": "-1"},
        "ring": {"HOROVOD_TPU_SHM": "0",
                 "HOROVOD_TPU_RING_THRESHOLD": "1"},
        "twolevel": {"HOROVOD_TWO_LEVEL": "1"},
    }
    buckets = [64 << 10, 1 << 20]
    big = 1 << 20
    grid = {}
    for nb in buckets:
        for alg, aenv in algs.items():
            for w in ("none", "bf16"):
                env = dict(aenv, HOROVOD_COMPRESSION=w,
                           HVD_BENCH_BYTES=str(nb))
                reps = 3 if nb == big else 1
                runs = sorted(
                    _run_world("compression", np_, timeout=600.0,
                               extra_env=env,
                               per_rank_env=hosts)["us_per_op"]
                    for _ in range(reps))
                key = f"{nb}/{alg}/{w}"
                grid[key] = {"us_per_op": runs[len(runs) // 2],
                             "runs": runs}
                print(f"  {key:>24}: {runs[len(runs) // 2]} us/op "
                      f"{runs}", flush=True)

    # Headline bf16-vs-none ratio, BOTH protocols (the zero_copy
    # section's doctrine for this throttling host):
    # * ISOLATED ALTERNATING legs — none/bf16/none/bf16/...: adjacent
    #   runs see similar throttle states, so the median of ADJACENT
    #   ratios is the undistorted isolated-leg speedup (grouped reps
    #   drift across the multi-second throttle phases);
    # * SIMULTANEOUS pairs — both worlds see the identical machine at
    #   every instant.
    iso_ratios = []
    for _ in range(3):
        a = _run_world("compression", np_, timeout=600.0,
                       extra_env=dict(algs["star"],
                                      HOROVOD_COMPRESSION="none",
                                      HVD_BENCH_BYTES=str(big)),
                       per_rank_env=hosts)
        b = _run_world("compression", np_, timeout=600.0,
                       extra_env=dict(algs["star"],
                                      HOROVOD_COMPRESSION="bf16",
                                      HVD_BENCH_BYTES=str(big)),
                       per_rank_env=hosts)
        iso_ratios.append(a["us_per_op"] / b["us_per_op"])
    iso_ratios.sort()

    pair_ratios = []
    for _ in range(3):
        pair = {}

        def _go(key, w):
            env = dict(algs["star"], HOROVOD_COMPRESSION=w,
                       HVD_BENCH_BYTES=str(big))
            pair[key] = _run_world("compression", np_, timeout=600.0,
                                   extra_env=env, per_rank_env=hosts)

        ta = threading.Thread(target=_go, args=("none", "none"))
        tb = threading.Thread(target=_go, args=("bf16", "bf16"))
        ta.start()
        tb.start()
        ta.join()
        tb.join()
        pair_ratios.append(pair["none"]["us_per_op"]
                           / pair["bf16"]["us_per_op"])
    pair_ratios.sort()

    bf16_star = iso_ratios[len(iso_ratios) // 2]
    # Algorithm comparison at the SAME wire dtype (orthogonal axes):
    # the headline number compares uncompressed algorithms. On this
    # one-host CI box "cross-host" links are loopback, so the star's
    # whole-path bf16 compression can beat two-level's cross-leg-only
    # compression — recorded per-dtype so real-fabric readers can see
    # both; on real DCN the cross links bound everything and the two
    # gains compound.
    tl_vs_flat_none = (
        min(grid[f"{big}/star/none"]["us_per_op"],
            grid[f"{big}/ring/none"]["us_per_op"])
        / grid[f"{big}/twolevel/none"]["us_per_op"])
    tl_vs_flat_bf16 = (
        min(grid[f"{big}/star/bf16"]["us_per_op"],
            grid[f"{big}/ring/bf16"]["us_per_op"])
        / grid[f"{big}/twolevel/bf16"]["us_per_op"])

    # Autotuner-convergence leg: bf16 proposed, shm on (so the
    # two-level candidate is feasible). Sample windows are LONG
    # (steps_per_sample=6, back-to-back ops) — an op-starved window
    # scores scheduler noise and the grid argmax inherits it.
    at = _run_world(
        "compression_autotune", np_, timeout=900.0,
        extra_env={"HOROVOD_AUTOTUNE": "1",
                   "HOROVOD_COMPRESSION": "bf16",
                   "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
                   "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "6",
                   "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": "4",
                   "HVD_BENCH_BYTES": str(big)},
        per_rank_env=hosts)
    # The comparison baseline re-runs the grid's best combo ADJACENT
    # in time to the tuned world (same throttle phase) — comparing
    # against a grid number measured minutes earlier mixes machine
    # phases, not configurations.
    best_key = min((k for k in grid if k.startswith(f"{big}/")),
                   key=lambda k: grid[k]["us_per_op"])
    _, best_alg, best_w = best_key.split("/")
    best_adj = _run_world(
        "compression", np_, timeout=600.0,
        extra_env=dict(algs[best_alg], HOROVOD_COMPRESSION=best_w,
                       HVD_BENCH_BYTES=str(big)),
        per_rank_env=hosts)
    best_us = best_adj["us_per_op"]
    frac = best_us / at["us_per_op"] if at["us_per_op"] else 0.0

    return {
        "world_size": np_,
        "hosts": np_ // 2,
        "cores": os.cpu_count(),
        "grid": grid,
        "pair_ratios_star_none_over_bf16":
            [round(r, 2) for r in pair_ratios],
        "isolated_ratios_star_none_over_bf16":
            [round(r, 2) for r in iso_ratios],
        "bf16_star_speedup": round(bf16_star, 2),
        "bf16_star_speedup_pass": bf16_star >= 1.5,
        "twolevel_vs_best_flat_none": round(tl_vs_flat_none, 2),
        "twolevel_vs_best_flat_bf16": round(tl_vs_flat_bf16, 2),
        "twolevel_pass": tl_vs_flat_none > 1.0,
        "twolevel_note": (
            "same-dtype comparison; on this one-host CI box the "
            "cross-host links are loopback, so whole-path star "
            "compression can outrun two-level's cross-leg-only "
            "compression at bf16 — on real DCN the cross links bound "
            "both and the gains compound"),
        "autotune": {**at, "best_grid_us_per_op": best_us,
                     "frac_of_best": round(frac, 3),
                     "meets_90pct": frac >= 0.9},
    }


def worker_autotune_value(rank: int, size: int) -> None:
    """Autotune VALUE demo (not just mechanics): a fusion-sensitive
    workload — many small allreduces per step — measured under (a)
    well-tuned defaults, (b) deliberately bad defaults (tiny fusion
    threshold: every tensor negotiates and executes alone), and
    (c) the same bad defaults with HOROVOD_AUTOTUNE=1, measured AFTER
    the Bayesian tuner converges. The orchestrator reports how much of
    the well-tuned throughput autotune recovers (scoring intent:
    reference parameter_manager.cc:145-171)."""
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import basics as _b

    hvd.init()
    rt = _b.runtime()
    xs = [np.full((AUTOTUNE_VALUE_BYTES // 4,), float(rank + 1),
                  np.float32) for _ in range(AUTOTUNE_VALUE_TENSORS)]

    def step(tag):
        hs = [hvd.allreduce_async(x, average=False,
                                  name=f"av.{tag}.{i}")
              for i, x in enumerate(xs)]
        for h in hs:
            hvd.synchronize(h)

    pm = rt.parameter_manager
    if pm is not None:
        # Drive traffic until the coordinator's tuner converges;
        # rank 0 broadcasts the done flag so every rank exits the
        # loop on the same iteration.
        converged = False
        for i in range(4000):
            step(f"c{i}")
            flag = 0.0 if rank != 0 else (0.0 if pm.tuning else 1.0)
            done = hvd.broadcast(np.asarray([flag]), root_rank=0,
                                 name=f"av.done/{i}")
            if float(done[0]) == 1.0:
                converged = True
                break
        if not converged:
            if rank == 0:
                print("RESULT " + json.dumps(
                    {"error": "autotune did not converge"}), flush=True)
            hvd.shutdown()
            return

    for i in range(3):
        step(f"w{i}")
    hvd.barrier(name="av.bar")
    times = []
    for i in range(AUTOTUNE_VALUE_STEPS):
        t0 = time.perf_counter()
        step(f"m{i}")
        times.append(time.perf_counter() - t0)
    _, med, _ = _quantiles(times)
    if rank == 0:
        out = {"steps_per_sec": round(1.0 / med, 3),
               "us_per_step": round(med * 1e6, 1),
               "tensors_per_step": AUTOTUNE_VALUE_TENSORS,
               "bytes_per_tensor": AUTOTUNE_VALUE_BYTES}
        if pm is not None:
            out["tuned_fusion_threshold_bytes"] = \
                pm.fusion_threshold_bytes()
            out["tuned_cycle_time_ms"] = round(pm.cycle_time_ms(), 2)
        print("RESULT " + json.dumps(out), flush=True)
    hvd.shutdown()


def _coordinator_cpu_bench() -> dict:
    """Pure-Python microbench of the coordinator's per-cycle CPU work —
    parse N RequestLists, count readiness, construct+fuse responses,
    serialize the ResponseList — with NO transport or scheduler in the
    way. This is the per-rank cost that actually grows with world size
    on the rank-0 host, free of the 1-vCPU time-share distortion that
    inflates the world-based overhead numbers."""
    import time as _t
    sys.path.insert(0, REPO)
    from horovod_tpu.common import wire
    from horovod_tpu.common.coordinator import (
        MessageTable, construct_response, fuse_responses)
    from horovod_tpu.common.message import (
        DataType, Request, RequestList, RequestType, ResponseList)

    out = {}
    for n_ranks in (8, 64, 256):
        tensors_per_cycle = 8  # a fused step's worth of requests
        payloads = []
        for r in range(n_ranks):
            reqs = [Request(request_rank=r,
                            request_type=RequestType.ALLREDUCE,
                            tensor_type=DataType.FLOAT32,
                            tensor_name=f"grad.{t}", root_rank=-1,
                            device=-1, tensor_shape=(1024,))
                    for t in range(tensors_per_cycle)]
            payloads.append(
                wire.serialize_request_list(RequestList(reqs)))
        iters = 50
        t0 = _t.perf_counter()
        for _ in range(iters):
            table = MessageTable()
            dtypes, slices = {}, {}
            for data in payloads:
                rl = wire.parse_request_list(data)
                for req in rl.requests:
                    dtypes[req.tensor_name] = req.tensor_type
                    slices[req.tensor_name] = 1
                    table.increment_tensor_count(req, n_ranks)
            responses = [construct_response(table, name, n_ranks)
                         for name in table.pop_ready()]
            fused = fuse_responses(responses, dtypes, 64 << 20, slices)
            wire.serialize_response_list(ResponseList(fused))
        per_cycle_us = (_t.perf_counter() - t0) / iters * 1e6
        out[str(n_ranks)] = {
            "cycle_us": round(per_cycle_us, 1),
            "us_per_rank": round(per_cycle_us / n_ranks, 2),
        }
    return out


# Chips per host assumed for pod-scale projections (a v5e host).
_CHIPS_PER_HOST = 8


def _hier_fanin(n: int, local: int = _CHIPS_PER_HOST) -> int:
    """Coordinator per-cycle fan-in under the hierarchical control
    plane: host-0's local leaves plus one aggregate channel per remote
    host (common/controller.py _setup_hierarchy)."""
    if n <= local:
        return n - 1  # single host: flat
    n_hosts = (n + local - 1) // local
    return (local - 1) + (n_hosts - 1)


def _project_scaling(overheads: dict, hier_overheads: dict,
                     step_budget_ms: float) -> dict:
    """Fit the measured control-plane overhead vs coordinator FAN-IN
    and project data-parallel scaling efficiency at pod scale.

    Model: the data plane rides ICI and overlaps with backward (as the
    reference's NCCL allreduce overlaps), so the per-step cost that
    does NOT parallelize is the negotiation round. What grows with
    scale is the coordinator's serial per-channel work — its fan-in.
    The flat star has fan-in N-1; the hierarchical control plane
    (default on multihost) drops it to local_leaves + n_hosts - 1, the
    same structural move MPI_Gather's tree makes for the reference
    (reference: operations.cc:1044-1065). Fit overhead = a + b*F on
    the flat measurements (F = N-1 at np 2/4/8), estimate the relay
    hop cost from the measured hierarchical worlds' residuals, then

        efficiency(N) ~= budget / (budget + a + b*F_hier(N) + hop)

    with budget the measured single-chip step time from bench.py and
    F_hier(64) = 14 for 8 hosts x 8 chips.
    """
    ns = sorted(int(k) for k in overheads)
    fs = [float(n - 1) for n in ns]  # flat fan-in
    ys = [overheads[str(n)]["barrier_us"] for n in ns]
    mean_f = sum(fs) / len(fs)
    mean_y = sum(ys) / len(ys)
    b = (sum((f - mean_f) * (y - mean_y) for f, y in zip(fs, ys))
         / sum((f - mean_f) ** 2 for f in fs))
    a = mean_y - b * mean_f
    # Relay hop cost: how much a measured hierarchical world exceeds
    # the pure fan-in prediction (extra leaf->root->coordinator hop;
    # on this 1-vCPU host it also absorbs the extra processes'
    # scheduling). The WORST residual is charged — deliberately
    # conservative. Clamp at 0 so noise can't make hierarchy look
    # better than the fan-in model allows.
    residuals = []
    hier_meas = {}
    for layout, d in hier_overheads.items():
        pred = a + b * d["fanin"]
        residuals.append(d["barrier_us"] - pred)
        hier_meas[layout] = {
            "barrier_us": d["barrier_us"], "fanin": d["fanin"],
            "fit_pred_us": round(pred, 1),
        }
    hop = max(0.0, max(residuals)) if residuals else 0.0
    budget_us = step_budget_ms * 1e3
    proj = {}
    for n in (8, 16, 64):
        f_hier = _hier_fanin(n)
        ov = a + b * f_hier + (hop if n > _CHIPS_PER_HOST else 0.0)
        ov_flat = a + b * (n - 1)
        proj[str(n)] = {
            "fanin": f_hier,
            "overhead_us": round(ov, 1),
            "efficiency": round(budget_us / (budget_us + ov), 4),
            "flat_overhead_us": round(ov_flat, 1),
            "flat_efficiency": round(
                budget_us / (budget_us + ov_flat), 4),
        }
    return {
        "measured_overhead_us": {str(n): overheads[str(n)]
                                 for n in ns},
        "measured_hier_overhead_us": hier_meas,
        "fit_us": {"a": round(a, 2), "b_per_channel": round(b, 2),
                   "relay_hop_us": round(hop, 1),
                   "model": ("a + b*fanin (+ relay hop when "
                             "hierarchical); flat fanin = N-1, hier "
                             "fanin = local_leaves + n_hosts - 1")},
        "chips_per_host": _CHIPS_PER_HOST,
        "step_budget_ms": step_budget_ms,
        "projected": proj,
        "note": (
            "overhead measured as a pure negotiation round (barrier) "
            "over the TCP control plane on loopback at np=2/4/8 flat "
            "plus np=8 hierarchical layouts (2x4, 4x2 fake hosts); "
            "the projection assumes the data plane (XLA collectives "
            "on ICI) overlaps with backward as in bench.py's measured "
            "step, so control-plane latency is the non-parallelizing "
            "term. step_budget_ms is bench.py's measured single-chip "
            "ResNet-50 step. Loopback TCP on a 1-vCPU host "
            "overstates per-channel cost vs a real pod's NIC-to-NIC "
            "fabric (and the hierarchical worlds' relay hop runs on "
            "the SAME starved core as every other rank there, where "
            "a real pod gives each host its own CPUs), making the "
            "64-chip number conservative."),
    }


def worker_bcast_render(rank: int, size: int) -> None:
    """Microbench the two XLA broadcast renderings on one process with
    8 virtual devices: masked psum (pre-r4: full allreduce bandwidth)
    vs the binary-tree collective-permute chain (the ncclBcast role,
    reference: nccl_operations.cc:334-351). Reports execution medians
    AND the compiled HLO's bytes-accessed estimate, which is
    machine-independent evidence that the permute rendering moves less
    data."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ndev = 8
    devs = jax.devices()[:ndev]
    mesh = Mesh(np.array(devs), ("p",))
    n = 1 << 20  # 4 MiB fp32 payload per device
    root = 0

    def masked(t):
        idx = jax.lax.axis_index("p")
        return jax.lax.psum(jnp.where(idx == root, t,
                                      jnp.zeros_like(t)), "p")

    def permute(t):
        # binary-tree chain, same shape as ops/xla_ops.py broadcast
        idx = jax.lax.axis_index("p")
        v = (idx - root) % ndev
        cur = t
        k = 1
        while k < ndev:
            perm = [((u + root) % ndev, (u + k + root) % ndev)
                    for u in range(k) if u + k < ndev]
            received = jax.lax.ppermute(cur, "p", perm=perm)
            cur = jnp.where((v >= k) & (v < 2 * k), received, cur)
            k *= 2
        return cur

    x = jax.device_put(
        np.ones((ndev * n,), np.float32),
        NamedSharding(mesh, P("p")))
    report = {"bytes": n * 4, "n_devices": ndev}
    for name, body in (("masked_psum", masked), ("ppermute", permute)):
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("p"),
                                   out_specs=P("p"), check_vma=False))
        compiled = fn.lower(x).compile()
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            report[f"{name}_bytes_accessed"] = ca.get("bytes accessed")
        except Exception:
            pass
        jax.block_until_ready(compiled(x))  # warmup
        ts = []
        for _ in range(ALLREDUCE_ITERS):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(x))
            ts.append((time.perf_counter() - t0) * 1e6)
        _, med, _ = _quantiles(ts)
        report[f"{name}_us"] = round(med, 1)
    if report.get("ppermute_us") and report.get("masked_psum_us"):
        report["speedup"] = round(
            report["masked_psum_us"] / report["ppermute_us"], 3)
    print("RESULT " + json.dumps(report), flush=True)


def worker_ragged_allgather(rank: int, size: int) -> None:
    """The fused variable-dim0 allgather's two renderings under heavy
    rank skew (1 big / 7 tiny), 8 virtual devices, one process: the
    padded all_gather moves N x max(dim0) while the masked-psum
    rendering moves ~2x the TRUE bytes (ops/xla_ops.py skew guard;
    reference behavior target: MPI_Allgatherv,
    mpi_operations.cc:95-173). Reports compiled bytes-accessed and
    execution medians — machine-independent evidence the guard's
    chosen side moves less data."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ndev = 8
    devs = jax.devices()[:ndev]
    mesh = Mesh(np.array(devs), ("p",))
    sn = 64                      # slice numel (row width)
    rows = [4096] + [1] * (ndev - 1)
    m = max(rows)
    # Every device's local shard is padded to max rows (SPMD inputs
    # share one shape); what differs is how much the COLLECTIVE moves.
    x = jax.device_put(np.ones((ndev * m * sn,), np.float32),
                       NamedSharding(mesh, P("p")))

    def padded(t):
        return jnp.ravel(jax.lax.all_gather(t, "p"))

    offs, acc = [], 0
    for r in range(ndev):
        offs.append(acc * sn)
        acc += rows[r]
    total = (acc + m) * sn
    offs_const = np.asarray(offs, np.int32)

    def psum_scatter(t):
        r = jax.lax.axis_index("p")
        buf = jnp.zeros((total,), t.dtype)
        buf = jax.lax.dynamic_update_slice(
            buf, t, (jnp.take(jnp.asarray(offs_const), r),))
        return jax.lax.psum(buf, "p")

    report = {"rows": rows, "slice_numel": sn, "n_devices": ndev,
              "true_MB": round(acc * sn * 4 / 1e6, 2),
              "padded_MB": round(ndev * m * sn * 4 / 1e6, 2)}
    for name, body in (("padded_gather", padded),
                       ("psum_scatter", psum_scatter)):
        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("p"),
                                   out_specs=P(), check_vma=False))
        compiled = fn.lower(x).compile()
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            report[f"{name}_bytes_accessed"] = ca.get("bytes accessed")
        except Exception:
            pass
        jax.block_until_ready(compiled(x))  # warmup
        ts = []
        for _ in range(ALLREDUCE_ITERS):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(x))
            ts.append((time.perf_counter() - t0) * 1e6)
        _, med, _ = _quantiles(ts)
        report[f"{name}_us"] = round(med, 1)
    pb = report.get("padded_gather_bytes_accessed")
    sb = report.get("psum_scatter_bytes_accessed")
    if pb and sb:
        report["bytes_ratio_padded_over_psum"] = round(pb / sb, 2)
    print("RESULT " + json.dumps(report), flush=True)


# -- kernel-side wire speed (PR 16: batched reactor, int8 codec, -------
# chunked relay) -------------------------------------------------------

KERNEL_GATHER_STEPS = 40
KERNEL_GATHER_BYTES = 16 << 10   # per-rank allgather block
KERNEL_RELAY_STEPS = 30
KERNEL_RELAY_BYTES = 1 << 20     # broadcast payload through the tree


def worker_kernel_gather(rank: int, size: int) -> None:
    """Batched-gather leg: an allgather loop on the socket star at
    ws=8 — every op the coordinator collects one frame from each of
    the other 7 ranks (the N-sequential-recvs pattern the reactor
    turns into one batched submission) and broadcasts the ~128 KiB
    world blob (over the MSG_ZEROCOPY threshold). Run in reactor-on /
    HOROVOD_TPU_REACTOR=0 pairs by the orchestrator; the wire bytes
    are identical, only how readiness is learned differs."""
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    n = KERNEL_GATHER_BYTES // 4
    x = np.full(n, float(rank), np.float32)
    for _ in range(5):
        hvd.allgather(x, name="kg")
    m0 = hvd.metrics()["local"]
    hvd.barrier(name="kg.b0")
    t0 = time.perf_counter()
    for _ in range(KERNEL_GATHER_STEPS):
        out = hvd.allgather(x, name="kg")
    hvd.barrier(name="kg.b1")
    elapsed = time.perf_counter() - t0
    m1 = hvd.metrics()["local"]
    assert np.asarray(out).nbytes == size * KERNEL_GATHER_BYTES
    report = {
        "bytes_per_rank": KERNEL_GATHER_BYTES,
        "steps": KERNEL_GATHER_STEPS,
        "us_per_op": round(elapsed * 1e6 / KERNEL_GATHER_STEPS, 1),
    }

    def _v(m, name):
        rec = m.get(name)
        if rec is None:
            return 0.0
        return rec["v"] if "v" in rec else rec.get("count", 0)

    if m1:
        report["data_copies"] = int(_v(m1, "hvd_data_copies_total")
                                    - _v(m0, "hvd_data_copies_total"))
        report["reactor_batches"] = int(
            _v(m1, "hvd_reactor_batch_size")
            - _v(m0, "hvd_reactor_batch_size"))
        report["zerocopy_sends"] = int(
            _v(m1, "hvd_zerocopy_sends_total")
            - _v(m0, "hvd_zerocopy_sends_total"))
    if os.environ.get("HVD_EXPECT_REACTOR") == "1" and rank == 0 and m1:
        from horovod_tpu import native as _nat
        if _nat.get() is not None:
            assert report.get("reactor_batches", 0) > 0, \
                "batched reactor never engaged (the A/B is vacuous)"
    if rank == 0:
        print("RESULT " + json.dumps(report), flush=True)
    hvd.shutdown()


def worker_kernel_relay(rank: int, size: int) -> None:
    """Chunked-relay leg: a 1 MiB broadcast loop on a 4-fake-host
    hierarchical world — the coordinator's frame reaches each host's
    local root, which forwards it to its leaves. With the reactor on,
    the root cuts through chunk-by-chunk (hvd_relay_frame, 256 KiB
    chunks) instead of store-and-forward; off restores the classic
    buffer-then-send relay, wire bytes identical."""
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    n = KERNEL_RELAY_BYTES // 4
    x = np.full(n, float(rank), np.float32)
    for _ in range(3):
        out = hvd.broadcast(x, root_rank=0, name="kr")
    m0 = hvd.metrics()["local"]
    hvd.barrier(name="kr.b0")
    t0 = time.perf_counter()
    for _ in range(KERNEL_RELAY_STEPS):
        out = hvd.broadcast(x, root_rank=0, name="kr")
    hvd.barrier(name="kr.b1")
    elapsed = time.perf_counter() - t0
    m1 = hvd.metrics()["local"]
    np.testing.assert_allclose(np.asarray(out)[0], 0.0)
    report = {
        "payload_bytes": KERNEL_RELAY_BYTES,
        "steps": KERNEL_RELAY_STEPS,
        "us_per_op": round(elapsed * 1e6 / KERNEL_RELAY_STEPS, 1),
    }
    if m1:
        def _v(m, name):
            rec = m.get(name)
            if rec is None:
                return 0.0
            return rec["v"] if "v" in rec else rec.get("count", 0)
        report["data_copies"] = int(_v(m1, "hvd_data_copies_total")
                                    - _v(m0, "hvd_data_copies_total"))
    if rank == 0:
        print("RESULT " + json.dumps(report), flush=True)
    hvd.shutdown()


def _kernel_codec_leg() -> dict:
    """Native int8 codec vs the numpy reference, in-process (no world
    needed: the codec is rank-local CPU work). Times the fused
    quantize+error-feedback pass and the dequantize pass on a 4 MiB
    f32 gradient against the classic numpy triple / astype-multiply
    round-trip, and spot-checks bit identity while at it."""
    import numpy as np
    sys.path.insert(0, REPO)
    from horovod_tpu import native as _nat
    from horovod_tpu.common import wire_dtype as wd

    if _nat.get() is None or not hasattr(_nat.get(), "hvd_quant8"):
        return {"skipped": "native core unavailable"}
    n = 1 << 20
    rng = np.random.RandomState(5)
    arr = rng.randn(n).astype(np.float32)
    res0 = (rng.randn(n) * 0.01).astype(np.float32)
    buf = np.empty(4 + n, np.uint8)
    out = np.empty(n, np.float32)
    reps = 21

    def _med(f):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            f()
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    # bit-identity spot check on fresh residual chains
    ref_buf = np.empty_like(buf)
    wd._quantize_numpy((arr + res0), ref_buf)
    nat_buf = np.empty_like(buf)
    r = res0.copy()
    assert _nat.quant8(arr, nat_buf, residual=r, residual_out=r)
    bit_identical = bool(nat_buf.tobytes() == ref_buf.tobytes())

    res_n = res0.copy()
    t_qn = _med(lambda: _nat.quant8(arr, buf, residual=res_n,
                                    residual_out=res_n))
    state = {"res": res0.copy()}

    def _np_triple():
        comp = arr + state["res"]
        wd._quantize_numpy(comp, buf)
        scale = float(buf[:4].view(np.float32)[0])
        sent = buf[4:].view(np.int8).astype(np.float32) \
            * np.float32(scale)
        state["res"] = comp - sent

    t_qp = _med(_np_triple)
    t_dn = _med(lambda: _nat.dequant8(buf, out))

    def _np_deq():
        scale = float(buf[:4].view(np.float32)[0])
        np.multiply(buf[4:].view(np.int8).astype(np.float32),
                    np.float32(scale), out=out)

    t_dp = _med(_np_deq)
    return {
        "elems": n,
        "reps": reps,
        "bit_identical": bit_identical,
        "quant_ef_native_us": round(t_qn * 1e6, 1),
        "quant_ef_numpy_us": round(t_qp * 1e6, 1),
        "quant_speedup": round(t_qp / t_qn, 2),
        "dequant_native_us": round(t_dn * 1e6, 1),
        "dequant_numpy_us": round(t_dp * 1e6, 1),
        "dequant_speedup": round(t_dp / t_dn, 2),
        "roundtrip_speedup": round((t_qp + t_dp) / (t_qn + t_dn), 2),
    }


def _kernel_gather_discipline_leg() -> dict:
    """The batched-submission claim, isolated: ws=8 star fan-in (7
    peer channels) with every peer's 16 KiB TAG_DATA frame already in
    its socket buffer, then time ONE hvd_gather_frames_batched drain
    against the 7 sequential Channel.recv_into calls it replaced (the
    exact reactor-off fallback discipline). Pre-queuing removes the
    peers' own send scheduling — on this one-core host a live world
    measures the scheduler, not the recv discipline — so the ratio is
    pure submission cost: 1 native call + one readiness batch vs 7
    (ctypes call + poll + read chain) round trips. Legs alternate
    rep-by-rep (drift-robust), median reported."""
    import ctypes as ct
    import socket

    import numpy as np
    sys.path.insert(0, REPO)
    from horovod_tpu import native as _nat
    from horovod_tpu.common import network

    lib = _nat.get()
    if lib is None or not hasattr(lib, "hvd_gather_frames_batched"):
        return {"skipped": "native core unavailable"}
    TAG_DATA = 4
    npeers = 7
    frame = 16 << 10
    reps = 41
    pairs = [socket.socketpair() for _ in range(npeers)]
    senders = [network.Channel(a, b"") for a, _ in pairs]
    recv_chans = [network.Channel(b, b"") for _, b in pairs]
    fds = (ct.c_int * npeers)(*[b.fileno() for _, b in pairs])
    payloads = [np.full(frame // 4, float(i), np.float32)
                for i in range(npeers)]
    bufs = [np.empty(frame, np.uint8) for _ in range(npeers)]
    bufptrs = (ct.c_void_p * npeers)(*[b.ctypes.data for b in bufs])
    caps = (ct.c_int64 * npeers)(*[frame] * npeers)
    lens = (ct.c_int64 * npeers)()
    done = (ct.c_uint8 * npeers)()
    arrive = (ct.c_double * npeers)()
    batches = (ct.c_int32 * npeers)()
    nb = ct.c_int(0)
    dev_idx = ct.c_int(-1)
    dev_buf = ct.POINTER(ct.c_uint8)()
    dev_len = ct.c_int64(0)
    dev_tag = ct.c_uint8(0)
    skip = (ct.c_uint8 * 1)(5)  # TAG_PING
    sec = (ct.c_uint8 * 1)()

    def _queue():
        for ch, p in zip(senders, payloads):
            ch.send(p, TAG_DATA)

    def _drain_batched():
        ct.memset(done, 0, npeers)
        nb.value = 0
        rc = lib.hvd_gather_frames_batched(
            fds, npeers, sec, 0, TAG_DATA, bufptrs, caps, lens,
            skip, 1, 5000, -1, _nat.NULL_ON_IDLE, done, arrive,
            batches, ct.byref(nb), ct.byref(dev_idx),
            ct.byref(dev_buf), ct.byref(dev_len), ct.byref(dev_tag))
        assert rc == 0, f"batched gather rc {rc}"

    def _drain_seq():
        for ch, b in zip(recv_chans, bufs):
            tag, n = ch.recv_into(b)
            assert tag == TAG_DATA and n == frame

    tb, ts = [], []
    for _ in range(reps):
        _queue()
        t0 = time.perf_counter()
        _drain_batched()
        tb.append(time.perf_counter() - t0)
        _queue()
        t0 = time.perf_counter()
        _drain_seq()
        ts.append(time.perf_counter() - t0)
    for a, b in pairs:
        a.close()
        b.close()
    tb.sort()
    ts.sort()
    mb, ms = tb[len(tb) // 2], ts[len(ts) // 2]
    flags = _nat.build_flags()
    return {
        "peers": npeers,
        "frame_bytes": frame,
        "reps": reps,
        "backend": "io_uring" if (flags & 0x2) else "poll",
        "batched_us": round(mb * 1e6, 1),
        "sequential_us": round(ms * 1e6, 1),
        "speedup": round(ms / mb, 2),
    }


def _kernel_relay_discipline_leg() -> dict:
    """The cut-through claim, isolated: one local root relaying a
    1 MiB upstream frame to its leaf (the 4-fake-host ws=8 shape) —
    hvd_relay_frame with the production 256 KiB chunks vs the classic
    store-and-forward it replaced (Channel.recv to a fresh bytes,
    then Channel.send per child). Sender and leaf drainers run as
    threads; the measured span covers the full relay op including
    the leaves' receipt. Legs alternate rep-by-rep, median."""
    import ctypes as ct
    import socket
    import threading

    import numpy as np
    sys.path.insert(0, REPO)
    from horovod_tpu import native as _nat
    from horovod_tpu.common import network

    lib = _nat.get()
    if lib is None or not hasattr(lib, "hvd_relay_frame"):
        return {"skipped": "native core unavailable"}
    TAG_DATA = 4
    nchild = 1
    frame = 1 << 20
    chunk = 256 << 10
    reps = 15
    up_a, up_b = socket.socketpair()
    kid_pairs = [socket.socketpair() for _ in range(nchild)]
    up_send = network.Channel(up_a, b"")
    up_recv = network.Channel(up_b, b"")
    relay_kid = [network.Channel(a, b"") for a, _ in kid_pairs]
    kid_recv = [network.Channel(b, b"") for _, b in kid_pairs]
    child_fds = (ct.c_int * nchild)(*[a.fileno() for a, _ in kid_pairs])
    payload = np.random.RandomState(0).randint(0, 255, frame, np.uint8)
    buf = np.empty(frame, np.uint8)
    win = (ct.c_uint8 * frame).from_buffer(buf)
    sec = (ct.c_uint8 * 1)()
    skip = (ct.c_uint8 * 2)(7, 8)  # TAG_METRICS, TAG_TRACE
    out_len = ct.c_int64(0)
    out_tag = ct.c_uint8(0)
    spill = ct.POINTER(ct.c_uint8)()

    def _sender():
        up_send.send(payload, TAG_DATA)

    def _drainer(ch):
        tag, data = ch.recv()
        assert tag == TAG_DATA and len(data) == frame

    def _spawn():
        th = [threading.Thread(target=_sender)]
        th += [threading.Thread(target=_drainer, args=(c,))
               for c in kid_recv]
        for t in th:
            t.start()
        return th

    def _run_cut_through():
        th = _spawn()
        t0 = time.perf_counter()
        rc = lib.hvd_relay_frame(
            up_b.fileno(), child_fds, nchild, TAG_DATA,
            ct.addressof(win), frame, sec, 0, skip, 2, chunk,
            5000, -1, ct.byref(out_len), ct.byref(out_tag),
            ct.byref(spill))
        assert rc == 0, f"relay rc {rc}"
        for t in th:
            t.join()
        return time.perf_counter() - t0

    def _run_classic():
        th = _spawn()
        t0 = time.perf_counter()
        tag, data = up_recv.recv()
        assert tag == TAG_DATA
        for c in relay_kid:
            c.send(data, TAG_DATA)
        for t in th:
            t.join()
        return time.perf_counter() - t0

    tc, tp = [], []
    for _ in range(reps):
        tc.append(_run_cut_through())
        tp.append(_run_classic())
    del win
    up_a.close()
    up_b.close()
    for a, b in kid_pairs:
        a.close()
        b.close()
    tc.sort()
    tp.sort()
    mc, mp = tc[len(tc) // 2], tp[len(tp) // 2]
    return {
        "children": nchild,
        "frame_bytes": frame,
        "chunk_bytes": chunk,
        "reps": reps,
        "cut_through_us": round(mc * 1e6, 1),
        "store_forward_us": round(mp * 1e6, 1),
        "speedup": round(mp / mc, 2),
    }


def _kernel_bench_section(np_: int) -> dict:
    """The PR 16 kernel-wire A/B: batched gather at ws=np_ on the
    socket star and the chunked hierarchical relay on 4 fake hosts,
    each reactor-on vs HOROVOD_TPU_REACTOR=0 (wire bytes identical,
    recv/send discipline differs), plus the in-process int8 codec
    timing. The headline ratios come from the ISOLATED discipline
    legs (pre-queued frames, alternating reps): a one-core host
    schedules one world process at a time, so live-world A/B numbers
    measure the scheduler and sit near 1.0 regardless of recv
    discipline — they are recorded as context. World protocols as
    for --steady-only: isolated alternating legs plus SIMULTANEOUS
    pairs."""
    import threading
    base = {"HOROVOD_TPU_SHM": "0", "HOROVOD_TPU_RING_THRESHOLD": "-1",
            "HOROVOD_TPU_METRICS": "1"}
    on_env = dict(base, HOROVOD_TPU_REACTOR="1", HVD_EXPECT_REACTOR="1")
    off_env = dict(base, HOROVOD_TPU_REACTOR="0")

    def _ab(mode, per_rank_env=None, iso_reps=3, pair_reps=2):
        iso_on, iso_off, iso_ratios = [], [], []
        for _ in range(iso_reps):
            a = _run_world(mode, np_, timeout=600.0, extra_env=on_env,
                           per_rank_env=per_rank_env)
            b = _run_world(mode, np_, timeout=600.0, extra_env=off_env,
                           per_rank_env=per_rank_env)
            iso_on.append(a)
            iso_off.append(b)
            iso_ratios.append(b["us_per_op"] / a["us_per_op"])
        ratios = []
        for _ in range(pair_reps):
            pair = {}

            def _go(key, env):
                pair[key] = _run_world(mode, np_, timeout=600.0,
                                       extra_env=env,
                                       per_rank_env=per_rank_env)

            ta = threading.Thread(target=_go, args=("on", on_env))
            tb = threading.Thread(target=_go, args=("off", off_env))
            ta.start()
            tb.start()
            ta.join()
            tb.join()
            ratios.append(pair["off"]["us_per_op"]
                          / pair["on"]["us_per_op"])
        iso_on.sort(key=lambda d: d["us_per_op"])
        iso_off.sort(key=lambda d: d["us_per_op"])
        iso_ratios.sort()
        ratios.sort()
        return {
            "reactor_on": iso_on[len(iso_on) // 2],
            "reactor_off": iso_off[len(iso_off) // 2],
            "isolated_ratios": [round(r, 2) for r in iso_ratios],
            "isolated_speedup": round(
                iso_ratios[len(iso_ratios) // 2], 2),
            "pair_ratios": [round(r, 2) for r in ratios],
        }

    gather_disc = _kernel_gather_discipline_leg()
    relay_disc = _kernel_relay_discipline_leg()
    gather = _ab("kernel_gather")
    relay = _ab("kernel_relay",
                per_rank_env=lambda r: {
                    "HOROVOD_HOSTNAME": f"fakehost{r // (np_ // 4)}"})
    codec = _kernel_codec_leg()
    out = {
        "world_size": np_,
        "cores": os.cpu_count(),
        "batched_gather": {"discipline": gather_disc, "world": gather},
        "int8_codec": codec,
        "hier_chunked_relay": {"discipline": relay_disc,
                               "world": relay},
    }
    if "speedup" in gather_disc:
        out["gather_meets_1_25x"] = gather_disc["speedup"] >= 1.25
    if "speedup" in relay_disc:
        out["relay_meets_1_2x"] = relay_disc["speedup"] >= 1.2
    if "roundtrip_speedup" in codec:
        out["codec_meets_1_3x"] = codec["roundtrip_speedup"] >= 1.3
    return out



def _run_single_proc(worker: str, timeout: float = 300.0) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker",
         worker, "--rank", "0", "--size", "1"],
        cwd=REPO, env=env, capture_output=True, timeout=timeout)
    out = p.stdout.decode()
    if p.returncode != 0:
        raise RuntimeError(f"{worker} exited {p.returncode}:\n"
                           f"{out}\n{p.stderr.decode()}")
    for line in out.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT from {worker}:\n{out}")


def _run_world(mode: str, size: int, timeout: float = 600.0,
               extra_env=None, per_rank_env=None,
               allow_rc=None) -> dict:
    """``allow_rc`` maps rank -> expected returncode for ranks that
    are SUPPOSED to die (the elastic section's fault-injected victim
    exits -SIGKILL by design)."""
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_CONTROLLER_ADDR"] = "127.0.0.1"
    env["HOROVOD_CONTROLLER_PORT"] = str(port)
    env["HOROVOD_SIZE"] = str(size)
    env.setdefault("HOROVOD_CYCLE_TIME", "1")
    # keep abort-path worlds (the elastic section SIGKILLs one) from
    # littering the checkout with flight-recorder postmortems
    env.setdefault("HOROVOD_TPU_FLIGHT_DIR",
                   tempfile.mkdtemp(prefix="hvd-flight-bench."))
    if extra_env:
        env.update(extra_env)
    procs = []
    for rank in range(size):
        e = dict(env)
        e["HOROVOD_RANK"] = str(rank)
        if per_rank_env:
            e.update(per_rank_env(rank))
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--worker", mode, "--rank", str(rank), "--size", str(size)],
            cwd=REPO, env=e, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT))
    outs = []
    for rank, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise RuntimeError(f"{mode} np={size} rank {rank} timed out")
        outs.append(out.decode())
        want = allow_rc.get(rank, 0) if allow_rc else 0
        if p.returncode != want:
            raise RuntimeError(
                f"{mode} np={size} rank {rank} exited {p.returncode}:\n"
                + outs[-1])
    for line in outs[0].splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line from rank 0:\n{outs[0]}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--np", type=int, default=8)
    ap.add_argument("--worker",
                    choices=["allreduce", "train", "fixed_compute",
                             "bcast_render", "ragged_allgather",
                             "overhead", "autotune_value", "cache",
                             "elastic", "compression",
                             "compression_autotune", "overlap",
                             "multitenant",
                             "kernel_gather", "kernel_relay",
                             "selfop_sync"])
    ap.add_argument("--rank", type=int)
    ap.add_argument("--size", type=int)
    ap.add_argument("--skip-variants", action="store_true",
                    help="only bench the default (shm) data plane")
    ap.add_argument("--cache-only", action="store_true",
                    help="run just the negotiation-cache A/B and merge "
                         "it into the existing RESULTS_cpu.json")
    ap.add_argument("--metrics-only", action="store_true",
                    help="run just the metrics-plane overhead A/B and "
                         "merge it into the existing RESULTS_cpu.json")
    ap.add_argument("--steady-only", action="store_true",
                    help="run just the zero-copy steady-bucket A/B "
                         "(HOROVOD_TPU_ZERO_COPY on/off) and merge it "
                         "into the existing RESULTS_cpu.json")
    ap.add_argument("--elastic", action="store_true",
                    help="run just the elastic recovery section "
                         "(steady us/op before a SIGKILL, the "
                         "re-rendezvous gap, us/op after the shrink; "
                         "recovery asserted < 2x heartbeat timeout) "
                         "and merge it into RESULTS_cpu.json")
    ap.add_argument("--overlap", action="store_true",
                    help="run just the overlap-tier A/B (bucketed "
                         "ready-order dispatch + in-flight cycles vs "
                         "the synchronous steady path, injected "
                         "compute calibrated to wire time; isolated + "
                         "simultaneous-pair protocols) and merge it "
                         "into RESULTS_cpu.json")
    ap.add_argument("--multitenant", action="store_true",
                    help="run just the multi-tenant section (two "
                         "tenants sharing one fleet vs an isolated "
                         "single-tenant baseline, isolated-leg "
                         "protocol, plus the 3:1 priority-weight "
                         "cycle-share shift) and merge it into "
                         "RESULTS_cpu.json")
    ap.add_argument("--kernel", action="store_true",
                    help="run just the kernel-side wire-speed A/B "
                         "(batched reactor gather at ws=np, chunked "
                         "hierarchical relay on np//2 fake hosts, "
                         "each vs HOROVOD_TPU_REACTOR=0; isolated + "
                         "simultaneous-pair protocols; plus the "
                         "in-process native int8 codec timing) and "
                         "merge it into RESULTS_cpu.json")
    ap.add_argument("--selfop", action="store_true",
                    help="run just the self-operation rejoin-sync A/B "
                         "(chunked tree-pipelined State.sync vs the "
                         "legacy per-key negotiated broadcast over "
                         "the same 64 MiB model-shaped state, socket "
                         "plane; zero-copy delta recorded) and merge "
                         "it into RESULTS_cpu.json")
    ap.add_argument("--compression", action="store_true",
                    help="run just the wire-compression/two-level "
                         "grid ((algorithm x dtype x bucket) medians "
                         "on a fake multi-host world, isolated + "
                         "simultaneous-pair protocols, plus the "
                         "autotuner-convergence run) and merge it "
                         "into RESULTS_cpu.json")
    args = ap.parse_args()

    if args.worker:
        {"allreduce": worker_allreduce,
         "train": worker_train,
         "fixed_compute": worker_fixed_compute,
         "bcast_render": worker_bcast_render,
         "ragged_allgather": worker_ragged_allgather,
         "autotune_value": worker_autotune_value,
         "cache": worker_cache,
         "elastic": worker_elastic,
         "compression": worker_compression,
         "compression_autotune": worker_compression_autotune,
         "overlap": worker_overlap,
         "multitenant": worker_multitenant,
         "kernel_gather": worker_kernel_gather,
         "kernel_relay": worker_kernel_relay,
         "selfop_sync": worker_selfop_sync,
         "overhead": worker_overhead}[args.worker](
             args.rank, args.size)
        return

    np_ = args.np
    cores = os.cpu_count() or 1
    results_path = os.path.join(REPO, "benchmarks", "RESULTS_cpu.json")

    if args.elastic:
        print(f"== elastic recovery (np={np_} -> {np_ - 1}, SIGKILL "
              f"at op {ELASTIC_BENCH_KILL_OP}) ==", flush=True)
        el = _elastic_bench_section(np_)
        print(f"  pre-kill {el['pre_kill_us_per_op']} us/op   "
              f"re-rendezvous gap {el['rendezvous_gap_ms']} ms "
              f"(budget {el['recovery_budget_ms']} ms)   "
              f"post-shrink {el['post_shrink_us_per_op']} us/op",
              flush=True)
        try:
            with open(results_path) as fh:
                merged = json.load(fh)
        except (OSError, ValueError):
            merged = {}
        merged["elastic_recovery"] = el
        with open(results_path, "w") as fh:
            json.dump(merged, fh, indent=2)
            fh.write("\n")
        print(f"merged elastic_recovery into {results_path}")
        return

    if args.selfop:
        np_so = min(np_, 4)
        mib = SELFOP_SYNC_KEYS * SELFOP_SYNC_KEY_ELEMS * 4 // 2**20
        print(f"== self-operation rejoin sync A/B (np={np_so}, "
              f"{SELFOP_SYNC_KEYS}-key {mib} MiB state, socket "
              f"plane) ==", flush=True)
        so = _selfop_bench_section(np_so)
        print(f"  fast {so['fast_sync_ms']} ms   legacy "
              f"{so['legacy_sync_ms']} ms   speedup {so['speedup']}x "
              f"(>=3x pass={so['meets_3x']})   data-copies delta "
              f"{so['fast_data_copies_delta']} "
              f"(clean={so['zero_copy_clean']})", flush=True)
        try:
            with open(results_path) as fh:
                merged = json.load(fh)
        except (OSError, ValueError):
            merged = {}
        merged["selfop"] = so
        with open(results_path, "w") as fh:
            json.dump(merged, fh, indent=2)
            fh.write("\n")
        print(f"merged selfop into {results_path}")
        return

    if args.multitenant:
        np_mt = min(np_, 4)  # ws>=4 per acceptance; 2 runtimes/proc
        print(f"== multi-tenant shared fleet (np={np_mt}, two tenants "
              f"spanning all ranks) ==", flush=True)
        mt = _multitenant_bench_section(np_mt)
        print(f"  isolated {mt['isolated_ops_per_s']} ops/s   shared "
              f"A {mt['shared_vs_isolated']['jobA']:.0%} / B "
              f"{mt['shared_vs_isolated']['jobB']:.0%} of isolated "
              f"(>=60% pass={mt['meets_60pct']})   3:1 share shift "
              f"{mt['share_shift_3to1_vs_equal']}x vs equal weights "
              f"(pass={mt['weights_shift_share']})", flush=True)
        try:
            with open(results_path) as fh:
                merged = json.load(fh)
        except (OSError, ValueError):
            merged = {}
        merged["multitenant"] = mt
        with open(results_path, "w") as fh:
            json.dump(merged, fh, indent=2)
            fh.write("\n")
        print(f"merged multitenant into {results_path}")
        return

    if args.kernel:
        print(f"== kernel-side wire speed A/B (np={np_}, "
              f"reactor on/off) ==", flush=True)
        kw = _kernel_bench_section(np_)
        g, r, c = (kw["batched_gather"], kw["hier_chunked_relay"],
                   kw["int8_codec"])
        print(f"  batched gather {g['discipline'].get('speedup', 'n/a')}x "
              f"(>=1.25 pass={kw.get('gather_meets_1_25x')}, "
              f"world {g['world']['isolated_speedup']}x)   "
              f"int8 codec roundtrip "
              f"{c.get('roundtrip_speedup', 'n/a')}x "
              f"(>=1.3 pass={kw.get('codec_meets_1_3x')}, "
              f"bit_identical={c.get('bit_identical')})   "
              f"chunked relay {r['discipline'].get('speedup', 'n/a')}x "
              f"(>=1.2 pass={kw.get('relay_meets_1_2x')}, "
              f"world {r['world']['isolated_speedup']}x)   copies on="
              f"{g['world']['reactor_on'].get('data_copies')}",
              flush=True)
        try:
            with open(results_path) as fh:
                merged = json.load(fh)
        except (OSError, ValueError):
            merged = {}
        merged["kernel_wire"] = kw
        with open(results_path, "w") as fh:
            json.dump(merged, fh, indent=2)
            fh.write("\n")
        print(f"merged kernel_wire into {results_path}")
        return

    if args.compression:
        print(f"== wire compression + two-level grid (np={np_}, "
              f"{np_ // 2} fake hosts) ==", flush=True)
        cp = _compression_bench_section(np_)
        print(f"  bf16 star speedup {cp['bf16_star_speedup']}x "
              f"(>=1.5 pass={cp['bf16_star_speedup_pass']})   "
              f"twolevel vs best flat "
              f"{cp['twolevel_vs_best_flat_none']}x @none / "
              f"{cp['twolevel_vs_best_flat_bf16']}x @bf16 "
              f"(pass={cp['twolevel_pass']})   autotuned "
              f"{cp['autotune']['frac_of_best']:.0%} of best grid "
              f"point (>=90% pass={cp['autotune']['meets_90pct']})",
              flush=True)
        try:
            with open(results_path) as fh:
                merged = json.load(fh)
        except (OSError, ValueError):
            merged = {}
        merged["compression"] = cp
        with open(results_path, "w") as fh:
            json.dump(merged, fh, indent=2)
            fh.write("\n")
        print(f"merged compression into {results_path}")
        return

    if args.overlap:
        print(f"== overlap tier A/B (np={np_}, compute ~= wire) ==",
              flush=True)
        ov = _overlap_bench_section(np_)
        print(f"  overlap {ov['overlap_on']['us_per_step']} us/step "
              f"vs flat {ov['overlap_off']['us_per_step']} us/step   "
              f"isolated speedup {ov['isolated_speedup']}x "
              f"(>=1.3 pass={ov['meets_1_3x']})   overlap fraction "
              f"{ov['overlap_fraction']} "
              f"(>=0.5 pass={ov['meets_fraction_50pct']})   "
              f"zero copies={ov['zero_copies']}", flush=True)
        try:
            with open(results_path) as fh:
                merged = json.load(fh)
        except (OSError, ValueError):
            merged = {}
        merged["overlap"] = ov
        with open(results_path, "w") as fh:
            json.dump(merged, fh, indent=2)
            fh.write("\n")
        print(f"merged overlap into {results_path}")
        return

    if args.steady_only:
        print(f"== zero-copy native data plane A/B (np={np_}, steady "
              f"bucket) ==", flush=True)
        zc = _zero_copy_bench_section(np_)
        print(f"  zero-copy on {zc['zero_copy_on']['us_per_op']} "
              f"us/op (native steady cycles "
              f"{zc['zero_copy_on'].get('native_steady_cycles')})   "
              f"off {zc['zero_copy_off']['us_per_op']} us/op   "
              f"speedup {zc.get('speedup')}x", flush=True)
        try:
            with open(results_path) as fh:
                merged = json.load(fh)
        except (OSError, ValueError):
            merged = {}
        merged["zero_copy_steady"] = zc
        with open(results_path, "w") as fh:
            json.dump(merged, fh, indent=2)
            fh.write("\n")
        print(f"merged zero_copy_steady into {results_path}")
        return

    if args.metrics_only:
        print(f"== metrics-plane overhead A/B (np={np_}, steady "
              f"bucket) ==", flush=True)
        mo = _metrics_bench_section(np_)
        print(f"  metrics off {mo['metrics_off']['us_per_op']} us/op"
              f"   on {mo['metrics_on']['us_per_op']} us/op   "
              f"enabled overhead {mo['enabled_overhead_pct']}%",
              flush=True)
        try:
            with open(results_path) as fh:
                merged = json.load(fh)
        except (OSError, ValueError):
            merged = {}
        merged["metrics_overhead"] = mo
        with open(results_path, "w") as fh:
            json.dump(merged, fh, indent=2)
            fh.write("\n")
        print(f"merged metrics_overhead into {results_path}")
        return

    if args.cache_only:
        print(f"== negotiation cache A/B (np={np_}, socket star) ==",
              flush=True)
        nc = _cache_bench_section(np_)
        print(f"  cache on {nc['cache_on']['us_per_op']} us/op "
              f"(hit rate {nc['cache_on'].get('hit_rate')})   off "
              f"{nc['cache_off']['us_per_op']} us/op   speedup "
              f"{nc.get('speedup')}x", flush=True)
        try:
            with open(results_path) as fh:
                merged = json.load(fh)
        except (OSError, ValueError):
            merged = {}
        merged["negotiation_cache"] = nc
        with open(results_path, "w") as fh:
            json.dump(merged, fh, indent=2)
            fh.write("\n")
        print(f"merged negotiation_cache into {results_path}")
        return

    sweeps = {}
    variant_names = ["shm"] if args.skip_variants else list(VARIANTS)
    for variant in variant_names:
        print(f"== allreduce medians (np={np_}, data plane: {variant}) "
              f"==", flush=True)
        coll = _run_world("allreduce", np_, extra_env=VARIANTS[variant])
        for row in coll["allreduce"]:
            print(f"  {row['bytes']:>9} B  {row['us_per_op']:>10} us  "
                  f"(p25 {row['us_p25']:>9} / p75 {row['us_p75']:>9})  "
                  f"bus {row['busbw_MBps']:>8} MB/s", flush=True)
        f = coll["fused"]
        print(f"  fused {f['tensors']}x{f['bytes'] // f['tensors']} B  "
              f"{f['us_per_batch']} us/batch  bus {f['busbw_MBps']} MB/s")
        sweeps[variant] = coll

    def _median_world(mode, size, runs=3):
        """Whole-world repeats: a single world can land entirely inside
        one of this host's multi-second stall windows (see module
        docstring), so the scaling legs take the median of three."""
        vals = [_run_world(mode, size)["steps_per_sec"]
                for _ in range(runs)]
        return {"steps_per_sec": sorted(vals)[len(vals) // 2],
                "runs": vals}

    print(f"== scaling (data-parallel MLP, real compute on "
          f"{cores} core(s)) ==", flush=True)
    t1 = _median_world("train", 1)
    tn = _median_world("train", np_)
    eff = tn["steps_per_sec"] / t1["steps_per_sec"]
    ideal = min(cores, np_) / np_
    print(f"  np=1: {t1['steps_per_sec']} steps/s   "
          f"np={np_}: {tn['steps_per_sec']} steps/s   "
          f"raw efficiency {eff:.1%}   "
          f"(ceiling on this host: {ideal:.1%} — compute time-shares "
          f"{cores} core(s); vs-achievable {min(eff / ideal, 1.0):.1%})",
          flush=True)

    bc = {}
    if not args.skip_variants:
        print("== broadcast rendering (8 virtual devices, 4 MiB) ==",
              flush=True)
        try:
            bc = _run_single_proc("bcast_render")
            print(f"  masked psum {bc.get('masked_psum_us')} us   "
                  f"ppermute {bc.get('ppermute_us')} us   "
                  f"speedup {bc.get('speedup')}x   bytes accessed "
                  f"{bc.get('masked_psum_bytes_accessed')} -> "
                  f"{bc.get('ppermute_bytes_accessed')}", flush=True)
        except Exception as e:
            # Record, don't abort: the already-measured sweeps must
            # still reach RESULTS_cpu.json.
            bc = {"error": repr(e)}
            print(f"  bcast_render failed: {e!r}", flush=True)

    rag = {}
    if not args.skip_variants:
        print("== ragged allgather skew guard (1 big / 7 tiny, 8 "
              "virtual devices) ==", flush=True)
        try:
            rag = _run_single_proc("ragged_allgather")
            print(f"  padded gather {rag.get('padded_gather_us')} us   "
                  f"psum scatter {rag.get('psum_scatter_us')} us   "
                  f"(true {rag.get('true_MB')} MB vs padded "
                  f"{rag.get('padded_MB')} MB)", flush=True)
        except Exception as e:
            rag = {"error": repr(e)}
            print(f"  ragged_allgather failed: {e!r}", flush=True)

    av = {}
    if not args.skip_variants:
        print("== autotune value (bad defaults -> tuned recovery, "
              "np=4) ==", flush=True)
        try:
            csv_path = os.path.join(REPO, "benchmarks",
                                    "autotune_value.csv")
            well = _run_world("autotune_value", 4, timeout=900.0)
            bad = _run_world("autotune_value", 4, timeout=900.0,
                             extra_env={
                                 "HOROVOD_FUSION_THRESHOLD": "1024"})
            rec = _run_world("autotune_value", 4, timeout=900.0,
                             extra_env={
                                 "HOROVOD_FUSION_THRESHOLD": "1024",
                                 "HOROVOD_AUTOTUNE": "1",
                                 "HOROVOD_AUTOTUNE_LOG": csv_path})
            av = {"well_tuned": well, "bad_defaults": bad,
                  "autotuned_from_bad": rec,
                  "autotune_log": "benchmarks/autotune_value.csv"}
            if "steps_per_sec" in well and "steps_per_sec" in bad:
                av["bad_fraction"] = round(
                    bad["steps_per_sec"] / well["steps_per_sec"], 3)
            if "steps_per_sec" in well and "steps_per_sec" in rec:
                av["recovered_fraction"] = round(
                    rec["steps_per_sec"] / well["steps_per_sec"], 3)
            print(f"  well-tuned {well.get('steps_per_sec')} steps/s   "
                  f"bad {bad.get('steps_per_sec')}   autotuned "
                  f"{rec.get('steps_per_sec')}   recovered "
                  f"{av.get('recovered_fraction')}", flush=True)
        except Exception as e:
            av = {"error": repr(e)}
            print(f"  autotune_value failed: {e!r}", flush=True)

    nc = {}
    if not args.skip_variants:
        print(f"== negotiation cache A/B (np={np_}, socket star) ==",
              flush=True)
        try:
            nc = _cache_bench_section(np_)
            print(f"  cache on {nc['cache_on']['us_per_op']} us/op "
                  f"(hit rate {nc['cache_on'].get('hit_rate')})   off "
                  f"{nc['cache_off']['us_per_op']} us/op   speedup "
                  f"{nc.get('speedup')}x", flush=True)
        except Exception as e:
            nc = {"error": repr(e)}
            print(f"  negotiation cache bench failed: {e!r}",
                  flush=True)

    mo = {}
    if not args.skip_variants:
        print(f"== metrics-plane overhead A/B (np={np_}, steady "
              f"bucket) ==", flush=True)
        try:
            mo = _metrics_bench_section(np_)
            print(f"  metrics off {mo['metrics_off']['us_per_op']} "
                  f"us/op   on {mo['metrics_on']['us_per_op']} us/op"
                  f"   enabled overhead "
                  f"{mo['enabled_overhead_pct']}%", flush=True)
        except Exception as e:
            mo = {"error": repr(e)}
            print(f"  metrics overhead bench failed: {e!r}",
                  flush=True)

    print(f"== scaling (fixed {FIXED_COMPUTE_S * 1e3:.0f} ms compute — "
          f"parallelizable, isolates comm overhead) ==", flush=True)
    f1 = _median_world("fixed_compute", 1)
    fn = _median_world("fixed_compute", np_)
    fc_eff = fn["steps_per_sec"] / f1["steps_per_sec"]
    print(f"  np=1: {f1['steps_per_sec']} steps/s   "
          f"np={np_}: {fn['steps_per_sec']} steps/s   "
          f"efficiency {fc_eff:.1%}", flush=True)

    projection = {}
    if not args.skip_variants:
        print("== control-plane overhead (negotiation round medians) "
              "==", flush=True)
        try:
            overheads = {}
            for n in sorted({2, 4, np_}):
                vals = [_run_world(
                    "overhead", n,
                    extra_env={"HOROVOD_TPU_HIER_CONTROLLER": "0"})
                    for _ in range(3)]
                vals.sort(key=lambda d: d["barrier_us"])
                overheads[str(n)] = vals[1]  # median of world medians
                print(f"  np={n}: barrier "
                      f"{overheads[str(n)]['barrier_us']} us   4KiB "
                      f"allreduce "
                      f"{overheads[str(n)]['small_allreduce_us']} us",
                      flush=True)
            # Hierarchical layouts at np=8: ranks grouped onto fake
            # hosts so leaves relay through their local root. Both
            # layouts have coordinator fan-in 4 (vs 7 flat).
            hier_overheads = {}
            for layout, per_host in (("2x4", 4), ("4x2", 2)):
                n_hosts = np_ // per_host
                fanin = (per_host - 1) + (n_hosts - 1)
                vals = [_run_world(
                    "overhead", np_,
                    extra_env={"HOROVOD_TPU_HIER_CONTROLLER": "1"},
                    per_rank_env=lambda r, ph=per_host: {
                        "HOROVOD_HOSTNAME": f"benchhost{r // ph}"})
                    for _ in range(3)]
                vals.sort(key=lambda d: d["barrier_us"])
                hier_overheads[layout] = dict(vals[1], fanin=fanin)
                print(f"  np={np_} hier {layout} (fan-in {fanin}): "
                      f"barrier {vals[1]['barrier_us']} us", flush=True)
            # step budget = bench.py's most recent single-chip
            # measurement (batch 256 at the reported img/s/chip)
            step_budget_ms = 103.6
            bench_files = sorted(
                f for f in os.listdir(REPO)
                if f.startswith("BENCH_r") and f.endswith(".json"))
            if bench_files:
                try:
                    with open(os.path.join(
                            REPO, bench_files[-1])) as fh:
                        parsed = json.load(fh).get("parsed") or {}
                    if parsed.get("value"):
                        step_budget_ms = round(
                            256.0 / parsed["value"] * 1e3, 2)
                except Exception:
                    pass
            projection = _project_scaling(overheads, hier_overheads,
                                          step_budget_ms)
            try:
                projection["coordinator_cpu"] = _coordinator_cpu_bench()
            except Exception as e:
                # a microbench failure must not discard the projection
                projection["coordinator_cpu"] = {"error": repr(e)}
            print(f"  fit {projection['fit_us']}   projected 64-chip "
                  f"efficiency "
                  f"{projection['projected']['64']['efficiency']:.1%}"
                  f" against a {step_budget_ms} ms step", flush=True)
            cc = projection["coordinator_cpu"]
            if "error" not in cc:
                print("  coordinator CPU (no transport): "
                      + "   ".join(f"np={n}: {v['cycle_us']} us/cycle"
                                   for n, v in cc.items()), flush=True)
        except Exception as e:
            projection = {"error": repr(e)}
            print(f"  overhead projection failed: {e!r}", flush=True)

    out = {
        "world_size": np_,
        "cpu_count": cores,
        "allreduce": sweeps["shm"]["allreduce"],
        "fused": sweeps["shm"]["fused"],
        "allreduce_variants": {
            v: sweeps[v]["allreduce"] for v in sweeps},
        "train_steps_per_sec": {"1": t1["steps_per_sec"],
                                str(np_): tn["steps_per_sec"]},
        "scaling_efficiency": round(eff, 4),
        "timeshare_ideal": round(ideal, 4),
        "efficiency_vs_achievable": round(min(eff / ideal, 1.0), 4),
        "broadcast_rendering": bc,
        "ragged_allgather": rag,
        "autotune_value": av,
        "negotiation_cache": nc,
        "metrics_overhead": mo,
        "projected_scaling": projection,
        "fixed_compute_ms": FIXED_COMPUTE_S * 1e3,
        "fixed_compute_steps_per_sec": {
            "1": f1["steps_per_sec"], str(np_): fn["steps_per_sec"]},
        "fixed_compute_scaling_efficiency": round(fc_eff, 4),
        "note": (
            "cpu_count==1 hosts time-share all ranks' compute on one "
            "core, capping steps_N/steps_1 at timeshare_ideal for ANY "
            "framework; fixed_compute_scaling_efficiency isolates the "
            "framework's communication overhead with parallelizable "
            "compute, and is the number comparable to the reference's "
            "published scaling efficiencies (one GPU per rank). The "
            "host additionally burst-throttles sustained CPU/memory "
            "load after ~1-2 s, which hits the 16 MiB shm/star legs "
            "specifically, so those rows vary several-fold between runs "
            "(e.g. shm 16 MiB medians of ~160-650 ms across "
            "sweeps); the ring's lower CPU intensity makes its "
            "16 MiB row the most stable, ~230-290 ms across runs."),
    }
    path = os.path.join(REPO, "benchmarks", "RESULTS_cpu.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2)
        fh.write("\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
