"""Multi-process integration tests: spawn N real processes that
negotiate through the TCP controller and move data through the socket
backend — the TPU build's version of the reference's ``mpirun -np 2
pytest`` legs (reference: .travis.yml:109-122, test/common.py:25-57)."""

import functools
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from tests import ahead

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def thread_pool_env(processes: int) -> dict:
    """torch's OpenMP pool for each of ``processes`` interpreters that
    run at once: its share of the cores this run may use, and two
    threads at most. Left alone the pool is a thread a core in EVERY
    rank, whose workers spin at each barrier; on a host whose cores are
    taken a spin waits out somebody else's time slice
    (``torch_synthetic_benchmark`` beside eight busy loops: 26 to 34 s
    at eight threads, 4 s at two), and at the suite's toy shapes the
    pool is the slower alone too (``torch_mnist`` 5.9 s at eight, 4.1 s
    at two). TensorFlow's pools neither spin nor gain: they stay as
    they are. The caller's own setting wins."""
    share = str(max(1, min(2, len(os.sched_getaffinity(0)) // processes)))
    return {name: os.environ.get(name, share)
            for name in ("OMP_NUM_THREADS", "MKL_NUM_THREADS")}


def _base_env(extra_env=None):
    """Worker-process env hygiene shared by every spawning test."""
    base = dict(os.environ)
    base["PYTHONPATH"] = REPO + os.pathsep + base.get("PYTHONPATH", "")
    base.setdefault("JAX_PLATFORMS", "cpu")
    # Arm the runtime lockdep (common/lockdep.py) in every spawned
    # world: all mp scenarios double as lock-inversion regression tests
    # — an acquisition-order inversion anywhere in the runtime raises
    # LockInversionError instead of someday deadlocking a real job.
    base.setdefault("HOROVOD_TPU_LOCKCHECK", "1")
    # Same deal for the thread-affinity sanitizer (common/threadcheck
    # .py): every checked field's cross-role write discipline is
    # re-proven by every spawned world, raising ThreadAffinityError
    # at the violating write instead of losing an update in prod.
    base.setdefault("HOROVOD_TPU_THREADCHECK", "1")
    # The default-on flight recorder dumps into CWD on every abort;
    # point every spawned world at a throwaway dir so abort-path tests
    # don't litter the checkout with pid-unique postmortems (tests
    # that assert on dumps override this with their own tmp_path).
    base.setdefault("HOROVOD_TPU_FLIGHT_DIR",
                    tempfile.mkdtemp(prefix="hvd-flight-test."))
    if extra_env:
        base.update(extra_env)
    return base


def _spawn_world(scenarios, size, timeout, extra_env=None,
                 per_rank_env=None):
    """One interpreter a rank, each running ``scenarios`` in turn (a
    port for each). Each rank also gets a hard in-process deadline a
    bit under ``timeout`` (HOROVOD_TEST_DEADLINE -> faulthandler alarm
    in mp_scenarios.main): a deadlocked rank self-reports with thread
    stacks instead of relying on this parent's kill."""
    ports = ",".join(str(_free_port()) for _ in scenarios)
    base = _base_env({**thread_pool_env(size), **(extra_env or {})})
    base.setdefault("HOROVOD_TEST_DEADLINE",
                    str(max(5.0, timeout - 5.0)))
    procs = []
    for rank in range(size):
        env = dict(base)
        if per_rank_env:
            env.update(per_rank_env(rank))
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "tests.mp_scenarios",
             ",".join(scenarios), str(rank), str(size), ports],
            cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    return procs


def run_scenario(scenario: str, size: int, timeout: float = 90.0,
                 extra_env=None, per_rank_env=None, expect_rc=None):
    """``expect_rc`` maps rank -> expected returncode for ranks that
    are SUPPOSED to die (fault-injection victims: a SIGKILL'd rank
    exits -9, not 0). Every other rank must exit 0."""
    procs = _spawn_world([scenario], size, timeout, extra_env,
                         per_rank_env)
    failures = []
    for rank, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError(
                f"scenario {scenario} rank {rank} timed out")
        want = 0 if expect_rc is None else expect_rc.get(rank, 0)
        if p.returncode != want:
            failures.append((rank, p.returncode, out.decode()))
    assert not failures, _ranks_said(failures)


def _ranks_said(ranks):
    return "\n".join(
        f"--- rank {r} exited {rc} ---\n{o}" for r, rc, o in ranks)


class ScenarioResults:
    """What one world made of several scenarios: ``check(name)`` is the
    assertion ``run_scenario(name, ...)`` would have made."""

    def __init__(self, scenarios, outputs):
        from tests.mp_scenarios import SCENARIO_DONE
        self._outputs = outputs     # rank -> (returncode, text)
        said = [text.splitlines() for _, text in outputs]
        # in the world's order: the first is the one that ended the
        # world, those after it never ran
        self._unfinished = [
            s for s in scenarios
            if not all(f"{SCENARIO_DONE} {s}" in lines for lines in said)]

    def check(self, scenario):
        if scenario not in self._unfinished:
            return
        first = self._unfinished[0]
        why = (f"scenario {scenario} failed" if scenario == first else
               f"scenario {scenario} did not run: {first}, earlier in "
               f"the same world, ended it")
        raise AssertionError(why + "\n" + _ranks_said(
            (r, rc, o) for r, (rc, o) in enumerate(self._outputs)))


def run_scenarios(scenarios, size: int, timeout: float = 170.0):
    """``scenarios`` in turn in ONE world's interpreters, so that what
    they import (a framework, seconds of it) is imported once a rank;
    each scenario still runs between its own ``hvd.init()`` and
    ``hvd.shutdown()``, and ``timeout`` (under a test's own limit) is
    for all of them together. Returns the ``ScenarioResults`` the tests
    read; nothing is asserted here, so each test fails or passes by its
    own scenario."""
    procs = _spawn_world(scenarios, size, timeout)
    outputs = [None] * size

    def drain(rank, p):
        out, _ = p.communicate()
        outputs[rank] = (p.returncode, out.decode())

    threads = [threading.Thread(target=drain, args=(r, p))
               for r, p in enumerate(procs)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + timeout
    while any(p.poll() is None for p in procs) \
            and time.monotonic() < deadline:
        if any(p.returncode for p in procs):
            # a rank that failed leaves its peers waiting for it at the
            # next rendezvous: what they had to say they soon have said
            deadline = min(deadline, time.monotonic() + 10.0)
        time.sleep(0.05)
    for p in procs:
        if p.poll() is None:
            p.kill()
    for t in threads:
        t.join()
    return ScenarioResults(list(scenarios), outputs)


# One world for the scenarios that can share one, not one a test: they
# run in turn in the same interpreters, which import what they need
# once (torch 2 s a rank, TensorFlow 5.5 s with bytecode and 10 s
# without, Keras on top of it, jax 2 s; an interpreter itself and this
# package 0.6 to 1 s). The first test to ask starts the world; each
# test reads its own scenario's result. A scenario that needs its own
# environment, exit codes or timeout, or whose failure is its point
# (the mismatch errors, a rank's death, the planted inversion), keeps
# a world of its own (``run_scenario``).

PLAIN = {2: ["allreduce", "allreduce_fused", "allreduce_multi_dtype",
             "allgather", "broadcast", "alltoall", "reducescatter",
             "barrier", "rank_subset_order", "topology", "jax_adapter",
             "scalar_broadcast"],
         3: ["allgather", "broadcast"],
         4: ["allreduce"]}


# tf_broadcast_hook turns eager execution off for its process, and so
# goes last
WORLDS = {
    **{("plain", size): (scenarios, size)
       for size, scenarios in PLAIN.items()},
    "torch": (["torch_optimizer", "torch_allreduce_grad", "torch_adam_state",
               "torch_opt_state_asymmetric"], 2),
    "tensorflow": (["keras_optimizer", "tf_tape", "tf_allreduce_grad",
                    "tf_sparse_as_dense", "tfkeras_facade",
                    "tf_broadcast_hook"], 2)}


def start_ahead(items):
    """The session's start (``tests/conftest.py``): the shared worlds
    that the selected tests take begin beside the in-process tests
    (``tests/ahead.py``; their scenarios assert on what they compute,
    none on the clock). A plain world's size is its test's ``size``, or
    two; a world nobody started is run by the first test that asks."""
    for item in items:
        for fixture in set(item.fixturenames) & {
                "plain_world", "torch_world", "tensorflow_world"}:
            name = fixture[:-len("_world")]
            if name == "plain":
                params = getattr(item, "callspec", None)
                name = (name, params.params.get("size", 2) if params else 2)
            ahead.start(("world", name), run_scenarios, *WORLDS[name])


def _world(name):
    return ahead.take(("world", name), run_scenarios, *WORLDS[name])


@pytest.fixture(scope="module")
def plain_world():
    """``plain_world(size)``: the world of that size with every
    scenario that needs nothing of its own."""
    return functools.cache(lambda size: _world(("plain", size)))


@pytest.fixture(scope="module")
def torch_world():
    return _world("torch")


@pytest.fixture(scope="module")
def tensorflow_world():
    return _world("tensorflow")


@pytest.mark.parametrize("size", [2, 4])
def test_allreduce(size, plain_world):
    plain_world(size).check("allreduce")


def test_allreduce_fused(plain_world):
    plain_world(2).check("allreduce_fused")


def test_allreduce_multi_dtype(plain_world):
    plain_world(2).check("allreduce_multi_dtype")


@pytest.mark.parametrize("size", [2, 3])
def test_allgather(size, plain_world):
    plain_world(size).check("allgather")


def test_broadcast(plain_world):
    plain_world(2).check("broadcast")


def test_broadcast_nonzero_root_three_ranks(plain_world):
    """size > 2 with every root: the root's payload must not be echoed
    back to it by the coordinator fan-out."""
    plain_world(3).check("broadcast")


def test_alltoall(plain_world):
    plain_world(2).check("alltoall")


def test_reducescatter(plain_world):
    plain_world(2).check("reducescatter")


def test_barrier(plain_world):
    plain_world(2).check("barrier")


def test_wide_world_smoke():
    """12 ranks on one host: the coordinator's fan-in (native poll
    gather), the shm plane, and FUSED batches all hold up beyond the
    2-4 rank worlds the rest of the suite uses."""
    world = run_scenarios(["allreduce", "allreduce_fused"], 12)
    world.check("allreduce")
    world.check("allreduce_fused")


@pytest.mark.time_limit(630)
def test_wide_world_hier_smoke():
    """16 ranks as 4 fake hosts x 4: the deepest hierarchy the suite
    runs — 3 local leaves + 3 aggregate root channels at the
    coordinator, 3-leaf relays at every remote root — with exact
    results on plain and FUSED batches."""
    run_scenario(
        "allreduce", 16, timeout=300.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 4}"})
    run_scenario(
        "allreduce_fused", 16, timeout=300.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 4}"})


@pytest.mark.parametrize("size", [3, 4])
def test_ring_allreduce(size):
    """Large payloads take the 2-phase ring data plane (threshold
    lowered so modest tensors cross it); mixed sizes exercise both
    paths against one established ring. Shm is disabled so the socket
    backend — the ring's host — is actually selected."""
    run_scenario("ring_allreduce", size, timeout=120.0,
                 extra_env={"HOROVOD_TPU_RING_THRESHOLD": "1024",
                            "HOROVOD_TPU_SHM": "0"})


def test_ring_establishment_failure_falls_back_to_star():
    run_scenario("ring_fallback", 3, timeout=120.0,
                 extra_env={"HOROVOD_TPU_RING_THRESHOLD": "1024",
                            "HOROVOD_TPU_SHM": "0"})


def test_shm_collectives():
    """Same-host world -> the shared-memory data plane carries every
    collective (reference analog: MPI_Win_allocate_shared staging,
    mpi_operations.cc:179-329)."""
    run_scenario("shm_collectives", 3, timeout=120.0)


def test_shm_establishment_failure_falls_back_to_socket():
    run_scenario("shm_fallback", 2, timeout=120.0)


def test_shm_disabled_for_multihost_topology():
    """Forced 2-host topology with ONE rank per host: nothing to gain
    from shared memory, the shm backend must stand down."""
    run_scenario(
        "shm_multihost_disabled", 2, timeout=120.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank}"})


def test_shm_hierarchical_allreduce_two_hosts():
    """4 ranks on 2 fake hosts: allreduce takes the hierarchical
    local-reduce -> cross-roots -> local-broadcast shm path."""
    run_scenario(
        "shm_hier_allreduce", 4, timeout=180.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


@pytest.mark.parametrize("scenario", [
    "allreduce", "allreduce_fused", "allgather", "broadcast",
    "alltoall", "reducescatter"])
def test_socket_backend_forced(scenario):
    """With shm disabled, every collective still runs correctly on the
    raw TCP socket backend (its default-world coverage moved to shm
    when that plane became the same-host default)."""
    run_scenario(scenario, 2, extra_env={"HOROVOD_TPU_SHM": "0"})


def test_shm_hierarchical_allreduce_uneven_hosts():
    """3 ranks split 2+1: the solo host's local reduce is the identity
    and its root still joins the cross exchange."""
    run_scenario(
        "shm_hier_allreduce", 3, timeout=180.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{min(rank, 1)}"})


def test_hier_controller_two_hosts():
    """4 ranks on 2 fake hosts: remote leaves migrate behind their
    local root, coordinator fan-in drops to 2, and the full collective
    mix stays exact end-to-end through the aggregated control plane."""
    run_scenario(
        "hier_controller", 4, timeout=180.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


def test_hier_controller_uneven_hosts():
    """5 ranks split 2+3: the remote host aggregates three ranks; the
    rank-order of frames inside the aggregate must survive."""
    run_scenario(
        "hier_controller", 5, timeout=180.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{min(rank // 2, 1)}"})


@pytest.mark.time_limit(270)
def test_hier_controller_three_hosts():
    """6 ranks on 3 fake hosts (2 each): multiple aggregate channels
    at the coordinator simultaneously."""
    run_scenario(
        "hier_controller", 6, timeout=240.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


def test_hier_controller_disabled_falls_back_flat():
    """HOROVOD_TPU_HIER_CONTROLLER=0 on the same topology keeps the
    flat star: no migration, no aggregate channels."""
    run_scenario(
        "flat_controller_multihost", 4, timeout=180.0,
        extra_env={"HOROVOD_TPU_HIER_CONTROLLER": "0"},
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


def test_shape_mismatch_error():
    run_scenario("shape_mismatch_error", 2)


def test_dtype_mismatch_error():
    run_scenario("dtype_mismatch_error", 2)


def test_root_rank_mismatch_error():
    run_scenario("root_rank_mismatch_error", 2)


def test_out_of_order_submission(plain_world):
    plain_world(2).check("rank_subset_order")


def test_topology(plain_world):
    plain_world(2).check("topology")


def test_stall_shutdown():
    run_scenario(
        "stall_shutdown", 2, timeout=60.0,
        extra_env={"HOROVOD_STALL_CHECK_TIME_SECONDS": "1",
                   "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": "2"})


def test_torch_distributed_optimizer(torch_world):
    torch_world.check("torch_optimizer")


def test_jax_adapter_host_path(plain_world):
    plain_world(2).check("jax_adapter")


def test_torch_allreduce_grad(torch_world):
    """Backward through hvd.allreduce matches the reference's autograd
    semantics."""
    torch_world.check("torch_allreduce_grad")


def test_torch_adam_state_broadcast(torch_world):
    torch_world.check("torch_adam_state")


def test_torch_opt_state_asymmetric_broadcast(torch_world):
    """Checkpoint-restore shape: only rank 0 has optimizer state; the
    broadcast must materialize worker state instead of hanging."""
    torch_world.check("torch_opt_state_asymmetric")


def test_keras_distributed_optimizer(tensorflow_world):
    tensorflow_world.check("keras_optimizer")


def test_tf_distributed_gradient_tape(tensorflow_world):
    tensorflow_world.check("tf_tape")


def test_tf_allreduce_grad(tensorflow_world):
    tensorflow_world.check("tf_allreduce_grad")


def test_tf_sparse_as_dense(tensorflow_world):
    """sparse_as_dense=True matches the IndexedSlices gather path
    bit-for-bit on an embedding gradient."""
    tensorflow_world.check("tf_sparse_as_dense")


def test_tf_broadcast_hook(tensorflow_world):
    """BroadcastGlobalVariablesHook drives a real TF1
    MonitoredTrainingSession broadcast."""
    tensorflow_world.check("tf_broadcast_hook")


@pytest.mark.slow
def test_tf_gather_bcast_grad():
    """Differentiable allgather (variable dim-0) and broadcast
    (root-only gradient), 3 ranks."""
    run_scenario("tf_gather_bcast_grad", 3, timeout=180.0)


def test_torch_gather_bcast_grad():
    """Same contract through the torch autograd Functions, plus the
    non-differentiable in-place broadcast_."""
    run_scenario("torch_gather_bcast_grad", 3, timeout=180.0)


def test_tfkeras_facade(tensorflow_world):
    tensorflow_world.check("tfkeras_facade")


def test_scalar_broadcast(plain_world):
    plain_world(2).check("scalar_broadcast")


@pytest.mark.parametrize("plane", ["shm", "socket"])
def test_mixed_op_storm(plane):
    """Async mixed-type collectives in per-rank-random submission
    order, on both host planes."""
    extra = {} if plane == "shm" else {"HOROVOD_TPU_SHM": "0"}
    run_scenario("mixed_op_storm", 3, timeout=120.0, extra_env=extra)


@pytest.mark.parametrize("plane", ["shm", "socket"])
def test_grouped_allreduce(plane):
    """Grouped submission: exact values per member, per-member average
    semantics, and all-or-nothing error surfacing with a usable world
    afterwards."""
    extra = {} if plane == "shm" else {"HOROVOD_TPU_SHM": "0"}
    run_scenario("grouped_allreduce", 3, timeout=120.0, extra_env=extra)


@pytest.mark.parametrize("plane", ["shm", "socket"])
def test_fused_allgather(plane):
    """ALLGATHER response fusion: multi-entry batches execute with
    entry-major displacement unpack on both host planes; mixed dtypes
    never share a batch."""
    extra = {"HOROVOD_CYCLE_TIME": "25"}
    if plane == "socket":
        extra["HOROVOD_TPU_SHM"] = "0"
    run_scenario("fused_allgather", 3, timeout=120.0, extra_env=extra)


def test_sparse_allgather_fusion():
    """word2vec-shaped sparse traffic (values+indices allgather pairs)
    executes as a few fused batches per step, not per-tensor singles."""
    run_scenario("sparse_allgather_fusion", 3, timeout=120.0,
                 extra_env={"HOROVOD_CYCLE_TIME": "25"})


def test_grouped_allreduce_atomic():
    """All group members land in ONE fused response even with the
    1 ms cycle ticking and a concurrent thread submitting singles."""
    run_scenario("grouped_atomic", 2, timeout=180.0)


@pytest.mark.time_limit(330)
@pytest.mark.parametrize("plane,ranks", [
    ("shm", 3), ("socket", 3), ("shm", 6)])
def test_coordinator_fuzz(plane, ranks):
    """240 seeded mixed collectives, per-rank-random submission order,
    overlapping waves, on both host planes (and a wider 6-rank world)
    — every value exact."""
    extra = {} if plane == "shm" else {"HOROVOD_TPU_SHM": "0"}
    run_scenario("coordinator_fuzz", ranks, timeout=300.0,
                 extra_env=extra)


@pytest.mark.parametrize("plane", ["shm", "socket"])
def test_response_cache_steady_state(plane):
    """Steady-state traffic negotiates through the bitmask fast path
    (hit rate ~100%, fully cached cycles observed), stays exact, keeps
    the cache bit-identical across ranks, and invalidates coherently
    on shape/dtype changes and skewed submission."""
    extra = {} if plane == "shm" else {"HOROVOD_TPU_SHM": "0"}
    run_scenario("response_cache_steady", 3, timeout=120.0,
                 extra_env=extra)


def test_response_cache_steady_state_hier_controller():
    """Same steady-state contract with the hit bitmasks AND-reduced at
    each fake host's local root before reaching the coordinator (the
    CACHED_AGG fold in the gather tree)."""
    run_scenario(
        "response_cache_steady", 4, timeout=180.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


def test_response_cache_capacity_eviction_coherent():
    """A tiny capacity forces constant LRU eviction; the eviction order
    (and thus slot reuse) must be world-identical and values exact —
    including names that come back after being evicted."""
    run_scenario("response_cache_eviction", 3, timeout=180.0,
                 extra_env={"HOROVOD_CACHE_CAPACITY": "8"})


def test_response_cache_disabled_via_env():
    """HOROVOD_CACHE_ENABLED=0 falls back to full negotiation on every
    rank (homogeneous) and the whole collective mix stays exact."""
    run_scenario("mixed_op_storm", 3, timeout=120.0,
                 extra_env={"HOROVOD_CACHE_ENABLED": "0"})


def test_response_cache_disabled_hier_two_rank_host():
    """Cache off + a 2-rank remote host: the local root relays an
    UNFOLDED per-rank pack on the request tag, whose raw leading
    byte (the u32 frame count, 2) collides with the CACHED_AGG kind —
    the PACKED envelope must disambiguate (regression: the coordinator
    once sniffed the count byte as a folded cache frame and aborted
    with a spurious divergence error)."""
    run_scenario(
        "mixed_op_storm", 4, timeout=180.0,
        extra_env={"HOROVOD_CACHE_ENABLED": "0"},
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


def test_response_cache_spec_hier_two_rank_host():
    """Speculative fused frames through a 2-rank remote host: payload
    frames cannot be mask-folded, so the root forwards them under the
    PACKED envelope and the coordinator still reduces the unanimous
    cycle inline — steady state, exact values, coherent caches."""
    run_scenario(
        "response_cache_steady", 4, timeout=180.0,
        extra_env={"HOROVOD_TPU_SHM": "0"},
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


def test_cache_control_plane_byte_budget():
    """Steady-state cycles must move O(capacity/8) control bytes per
    rank — a counting wrapper on Channel send/recv asserts the
    per-cycle budget on a worker rank at world_size=4 (speculative
    fused frames carry tensor data on the request tag by design, so
    they are disabled to expose the mask-path budget)."""
    run_scenario("cache_byte_budget", 4, timeout=180.0,
                 extra_env={"HOROVOD_CACHE_CAPACITY": "256",
                            "HOROVOD_CACHE_SPECULATIVE": "0"})


def test_response_cache_heterogeneous_speculation_knob():
    """HOROVOD_CACHE_SPECULATIVE off on ONE rank only: the fused
    single-round path requires unanimity per cycle, so the world
    falls back to the classic two-round cached path everywhere —
    correct results, zero completed speculative cycles."""
    run_scenario(
        "response_cache_hetero_spec", 3, timeout=120.0,
        extra_env={"HOROVOD_TPU_SHM": "0"},
        per_rank_env=lambda rank: (
            {"HOROVOD_CACHE_SPECULATIVE": "0"} if rank == 1 else {}))


@pytest.mark.time_limit(330)
def test_kitchen_sink_all_subsystems(tmp_path):
    """Cross-subsystem integration: autotune (+log), timeline (+cycle
    marks), hierarchical shm over a fake 2-host topology, and the stall
    inspector armed — all in one 4-rank world under shuffled mixed
    traffic with a mid-stream coordinator ERROR. Afterwards both
    artifacts must be well-formed: the timeline is valid Chrome-tracing
    JSON with negotiation + execution + cycle vocabulary, and the
    autotune CSV has sample rows."""
    timeline = str(tmp_path / "ks_timeline.json")
    atlog = str(tmp_path / "ks_autotune.csv")
    run_scenario(
        "kitchen_sink", 4, timeout=300.0,
        extra_env={
            "HOROVOD_TIMELINE": timeline,
            "HOROVOD_TIMELINE_MARK_CYCLES": "1",
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_LOG": atlog,
            # first CSV row needs (warmup + 3 median scores) busy
            # cycles per sampled step; with the defaults that is 40
            # cycles, which the storm's fused/cached traffic does not
            # deterministically produce (the pre-PR-20 flake). One
            # step per sample + one warmup sample = 4 busy cycles,
            # well under the 20 rounds the scenario always drives.
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "1",
            "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
            "HOROVOD_HIERARCHICAL_ALLGATHER": "1",
            "HOROVOD_STALL_CHECK_TIME_SECONDS": "60",
        },
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})

    import json
    with open(timeline) as f:
        events = json.load(f)
    names = {e.get("name") for e in events}
    for required in ("NEGOTIATE_ALLREDUCE", "NEGOTIATE_BROADCAST",
                     "NEGOTIATE_ALLGATHER", "ALLREDUCE", "BROADCAST",
                     "CYCLE_START"):
        assert required in names, (required, sorted(names)[:40])

    with open(atlog) as f:
        rows = [ln for ln in f.read().splitlines() if ln.strip()]
    assert len(rows) >= 2, rows  # header + at least one sample


@pytest.mark.parametrize("plane", ["shm", "socket"])
def test_bf16_host_path(plane):
    extra = {} if plane == "shm" else {"HOROVOD_TPU_SHM": "0"}
    run_scenario("bf16_host_path", 2, extra_env=extra)


def test_secret_mismatch_fails_init_loudly():
    """Ranks with different HOROVOD_SECRET_KEY must fail init with
    authentication/timeout errors, never connect or hang (reference
    analog: the launcher's per-run HMAC secret contract)."""
    port = _free_port()
    base = _base_env({"HOROVOD_CONTROLLER_ADDR": "127.0.0.1",
                      "HOROVOD_CONTROLLER_PORT": str(port),
                      "HOROVOD_SIZE": "2",
                      "HOROVOD_START_TIMEOUT": "3"})
    code = "import horovod_tpu as hvd; hvd.init()"
    procs = []
    for rank in range(2):
        env = dict(base, HOROVOD_RANK=str(rank),
                   HOROVOD_SECRET_KEY="alpha" if rank == 0 else "beta")
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=60)
            outs.append(out.decode())
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    assert all(p.returncode != 0 for p in procs), outs
    assert "ranks connected" in outs[0] or "Timeout" in outs[0], outs[0]
    assert ("ConnectionError" in outs[1] or "HMAC" in outs[1]
            or "closed" in outs[1]), outs[1]


@pytest.mark.parametrize("plane", ["shm", "socket"])
def test_edge_shapes(plane):
    """Zero-size and 0-d tensors through every collective, on both
    host data planes."""
    extra = {} if plane == "shm" else {"HOROVOD_TPU_SHM": "0"}
    run_scenario("edge_shapes", 3, extra_env=extra)


def test_lockcheck_catches_synthetic_inversion():
    """Every mp world runs with HOROVOD_TPU_LOCKCHECK=1 (see
    _base_env); this scenario additionally PROVOKES an inversion and
    asserts the armed lockdep raises it on every rank while real
    collectives stay inversion-free before and after."""
    run_scenario("lockcheck_inversion", 2)


def test_rank_death_fails_survivors_cleanly():
    """Kill one of three ranks mid-job: the other two must error out
    with HorovodInternalError on their next collective, not hang."""
    run_scenario("rank_death", 3, timeout=60.0)


def test_coordinator_death_fails_workers_cleanly():
    """Kill rank 0 (coordinator + controller host): both workers must
    error out on their next collective and shut down, not hang."""
    run_scenario("coordinator_death", 3, timeout=60.0)


def test_rank_death_hier_leaf_fails_survivors_cleanly():
    """Kill a remote LEAF under the hierarchical control plane (4
    ranks, 2 fake hosts): the death must propagate leaf -> local root
    -> coordinator -> world without hanging any tier."""
    run_scenario(
        "rank_death_hier", 4, timeout=90.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


# -- fail-fast world abort (heartbeats + ABORT fan-out; see -----------
# docs/fault_tolerance.md). Victims die by fault injection armed via
# HOROVOD_FAULT_SPEC (horovod_tpu/common/faults.py); survivors must
# raise WorldAbortedError NAMING the dead rank, purely in-band — the
# harness timeout/alarm exists only to report a regression, never to
# unblock a passing run.

_HB_ENV = {
    "HOROVOD_HEARTBEAT_INTERVAL": "0.3",
    "HOROVOD_HEARTBEAT_TIMEOUT": "3",
}
_SIGKILL_RC = -signal.SIGKILL


def test_abort_sigkill_leaf_mid_allreduce():
    """SIGKILL rank 1 of 3 just before it executes its 3rd collective:
    both survivors (coordinator included) raise WorldAbortedError
    naming rank 1 within the detection deadline."""
    run_scenario(
        "abort_sigkill_leaf", 3, timeout=60.0,
        extra_env={**_HB_ENV,
                   "HOROVOD_FAULT_SPEC": "rank=1:kill:op=3"},
        expect_rc={1: _SIGKILL_RC})


def test_abort_sigkill_local_root_hier():
    """SIGKILL the second fake host's local root (rank 2 of 4)
    mid-collective: leaves below it, the coordinator above it, and
    the unrelated host's ranks all abort with rank 2 named."""
    run_scenario(
        "abort_sigkill_local_root", 4, timeout=60.0,
        extra_env={**_HB_ENV,
                   "HOROVOD_FAULT_SPEC": "rank=2:kill:op=3"},
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"},
        expect_rc={2: _SIGKILL_RC})


def test_abort_sigkill_coordinator():
    """SIGKILL rank 0 (coordinator + controller socket) mid-
    collective: with no coordinator left to fan the ABORT, each worker
    must detect its dead upward channel itself and name rank 0."""
    run_scenario(
        "abort_sigkill_coordinator", 3, timeout=60.0,
        extra_env={**_HB_ENV,
                   "HOROVOD_FAULT_SPEC": "rank=0:kill:op=3"},
        expect_rc={0: _SIGKILL_RC})


def test_abort_sigkill_mid_cached_cycle():
    """SIGKILL rank 1 deep in bitmask steady state (op=40 of a
    single-tensor loop is long past warmup): the survivors are blocked
    in a bits-frame gather when the victim dies, and must still raise
    WorldAbortedError naming rank 1 within the heartbeat deadline —
    the PR 2 fail-fast invariant holds on the negotiation fast path."""
    run_scenario(
        "abort_sigkill_cached", 3, timeout=60.0,
        extra_env={**_HB_ENV,
                   "HOROVOD_FAULT_SPEC": "rank=1:kill:op=40"},
        expect_rc={1: _SIGKILL_RC})


def test_native_steady_zero_copy_socket():
    """Zero-copy native steady cycle on the socket star at ws=4:
    exact values, native_steady_cycles advancing everywhere, zero
    fallback byte-copies after warmup, and the aliasing contract
    (outputs from step k survive 19 later steps untouched)."""
    run_scenario(
        "native_steady", 4, timeout=120.0,
        extra_env={"HOROVOD_TPU_SHM": "0",
                   "HOROVOD_TPU_METRICS": "1"})


def test_native_steady_alloc_property_shm():
    """The O(1)-allocations steady-step property on the shm data
    plane: hvd_data_copies_total must not move across 25 steady
    steps (the shm plane never defensively copies payload bytes)."""
    run_scenario(
        "native_steady", 4, timeout=120.0,
        extra_env={"HOROVOD_TPU_METRICS": "1"})


def test_native_steady_pure_python_fallback():
    """HOROVOD_NATIVE=0: the whole steady machinery must stay green
    on the pure-Python paths (classic PR 3 fused cycle)."""
    run_scenario(
        "native_steady", 3, timeout=120.0,
        extra_env={"HOROVOD_TPU_SHM": "0",
                   "HOROVOD_TPU_METRICS": "1",
                   "HOROVOD_NATIVE": "0"})


def test_native_hetero_world():
    """Mixed world: rank 1 runs with the native core off, rank 2 with
    HOROVOD_TPU_ZERO_COPY=0 — the CACHED_SPEC wire format is
    byte-identical either way, so values stay exact, fused cycles
    still complete, and the native coordinator keeps its one-call
    steady loop over pure-Python peers."""
    run_scenario(
        "native_hetero", 4, timeout=120.0,
        extra_env={"HOROVOD_TPU_SHM": "0"},
        per_rank_env=lambda rank: (
            {"HOROVOD_NATIVE": "0"} if rank == 1 else
            {"HOROVOD_TPU_ZERO_COPY": "0"} if rank == 2 else {}))


def test_abort_sigkill_mid_native_steady():
    """SIGKILL rank 1 deep in zero-copy steady state (op=40): the
    survivors are blocked inside hvd_steady_worker/coord when the
    victim dies, and must still raise WorldAbortedError naming rank 1
    within the heartbeat deadline — the C loop honors the armed recv
    deadlines."""
    run_scenario(
        "abort_sigkill_native_steady", 3, timeout=60.0,
        extra_env={**_HB_ENV,
                   "HOROVOD_TPU_SHM": "0",
                   "HOROVOD_FAULT_SPEC": "rank=1:kill:op=40"},
        expect_rc={1: _SIGKILL_RC})


def test_abort_sever_mid_native_steady():
    """Abruptly close rank 1's upward control channel deep in
    zero-copy steady state: both sides of the cut converge on a
    structured world abort instead of blocking in the native loop."""
    run_scenario(
        "abort_sever_native_steady", 3, timeout=60.0,
        extra_env={**_HB_ENV,
                   "HOROVOD_TPU_SHM": "0",
                   "HOROVOD_FAULT_SPEC": "rank=1:sever:cycle=30"})


def test_abort_heartbeat_detects_silent_hang():
    """Wedge rank 1's background loop for 6 s WITHOUT killing it (no
    FIN/RST ever reaches the peers — the case TCP error detection
    cannot see): survivors must abort within the 3 s heartbeat
    deadline plus slack, naming rank 1, proving detection is bounded
    by HOROVOD_HEARTBEAT_TIMEOUT rather than by the wedge ending."""
    run_scenario(
        "abort_heartbeat_hang", 3, timeout=60.0,
        extra_env={**_HB_ENV,
                   "HOROVOD_FAULT_SPEC":
                       "rank=1:hang:cycle=20:seconds=6"})


def test_abort_severed_control_link():
    """Fault-inject an abrupt close of rank 1's upward control channel
    (process stays alive): both sides of the cut converge on a world
    abort instead of one side blocking forever."""
    run_scenario(
        "abort_severed_link", 3, timeout=60.0,
        extra_env={**_HB_ENV,
                   "HOROVOD_FAULT_SPEC": "rank=1:sever:cycle=20"})


def test_abort_sigkill_ring_data_plane():
    """SIGKILL rank 1 while payloads ride the 2-phase RING data plane
    (threshold lowered so they do): the survivor whose ring link dies
    must blame the dead NEIGHBOR — not itself, the healthy detecting
    rank — and the abort must still fan to every survivor."""
    run_scenario(
        "abort_sigkill_ring", 3, timeout=60.0,
        extra_env={**_HB_ENV,
                   "HOROVOD_TPU_RING_THRESHOLD": "1024",
                   "HOROVOD_TPU_SHM": "0",
                   "HOROVOD_FAULT_SPEC": "rank=1:kill:op=3"},
        expect_rc={1: _SIGKILL_RC})


@pytest.mark.time_limit(270)
def test_ring_data_plane_with_hier_controller():
    """Large payloads on the TCP ring while the CONTROL plane is
    hierarchical: ring rendezvous (listener ports via relayed
    gather/broadcast, peer IPs via the owner-channel map) must still
    connect every rank."""
    run_scenario(
        "ring_allreduce", 4, timeout=240.0,
        extra_env={"HOROVOD_TPU_RING_THRESHOLD": "1024",
                   "HOROVOD_TPU_SHM": "0"},
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


# -- overlap tier (HOROVOD_OVERLAP_*: bucketed ready-order dispatch +
# in-flight steady cycles + chunked pipelined transfer;
# docs/performance.md Layer 5). Rank-local scheduling only — the wire
# protocol is unchanged, so heterogeneous knobs must degrade to the
# synchronous path instead of diverging.

_OVERLAP_ENV = {
    "HOROVOD_TPU_SHM": "0",
    "HOROVOD_TPU_METRICS": "1",
    "HOROVOD_OVERLAP_BUCKETS": "4",
    "HOROVOD_OVERLAP_INFLIGHT": "2",
}


def test_overlap_steady_socket():
    """Bucketed grouped allreduce at ws=4: exact sums, multiple
    steady masks, overlap cycles advancing through the in-flight
    runner, hvd_data_copies_total still zero once steady."""
    run_scenario("overlap_steady", 4, timeout=120.0,
                 extra_env=dict(_OVERLAP_ENV))


def test_overlap_steady_compressed_chunked():
    """Same loop under bf16 wire compression with a tiny chunk size:
    every steady cycle rides hvd_steady_worker_chunked (cast
    interleaved with the send) and the values — small integers,
    exactly representable in bf16 — stay exact."""
    run_scenario("overlap_steady", 4, timeout=120.0,
                 extra_env=dict(_OVERLAP_ENV,
                                HOROVOD_COMPRESSION="bf16",
                                HOROVOD_OVERLAP_CHUNK_BYTES="512"))


def test_overlap_bitexact_vs_flat():
    """Bucketed ws=4 training is bit-exact vs an unbucketed replay of
    the same step stream (rounding-sensitive f32 values)."""
    run_scenario("overlap_bitexact", 4, timeout=120.0,
                 extra_env=dict(_OVERLAP_ENV))


def test_overlap_hetero_knobs_degrade():
    """Ranks disagree on every overlap knob: bucket counts differ,
    one rank runs fully synchronous — grants degrade to mask
    intersections and results stay exact and cache-coherent."""
    run_scenario(
        "overlap_hetero", 4, timeout=120.0,
        extra_env=dict(_OVERLAP_ENV),
        per_rank_env=lambda rank: {
            1: {"HOROVOD_OVERLAP_INFLIGHT": "0",
                "HOROVOD_OVERLAP_BUCKETS": "0"},
            2: {"HOROVOD_OVERLAP_BUCKETS": "2"},
        }.get(rank, {}))


def test_overlap_sigkill_mid_inflight():
    """SIGKILL rank 1 deep in bucketed steady state — buckets are in
    flight on the overlap runner when the victim dies. Survivors must
    raise WorldAbortedError naming rank 1 within the deadline."""
    run_scenario(
        "overlap_sigkill", 3, timeout=60.0,
        extra_env=dict(_OVERLAP_ENV, **_HB_ENV,
                       HOROVOD_FAULT_SPEC="rank=1:kill:op=60"),
        expect_rc={1: _SIGKILL_RC})


def test_overlap_sever_mid_inflight():
    """Severed control link while the overlap runner drives native
    cycles: survivors converge on a structured world abort."""
    run_scenario(
        "overlap_sever", 3, timeout=60.0,
        extra_env=dict(_OVERLAP_ENV, **_HB_ENV,
                       HOROVOD_FAULT_SPEC="rank=1:sever:cycle=40"))


# -- elastic worlds (HOROVOD_ELASTIC=1; survive preemption and -------
# re-rendezvous instead of aborting — docs/fault_tolerance.md). The
# victims die by fault injection; the SURVIVORS must re-form a smaller
# world and keep computing EXACT collectives, all under the
# HOROVOD_TEST_DEADLINE alarm guard like every other mp scenario.

_ELASTIC_ENV = {
    **_HB_ENV,
    "HOROVOD_ELASTIC": "1",
    "HOROVOD_ELASTIC_WINDOW": "10",
}


@pytest.mark.parametrize("plane", ["shm", "socket"])
def test_elastic_shrink_survives_sigkill(plane):
    """SIGKILL one of four ranks mid-collective: survivors
    re-rendezvous into ws=3 within 2x the heartbeat timeout and
    complete >= 20 more collectives whose allreduce results match a
    fresh ws=3 world bit-for-bit — on the shm AND socket planes."""
    extra = dict(_ELASTIC_ENV,
                 HOROVOD_FAULT_SPEC="rank=3:kill:op=12",
                 HOROVOD_TPU_METRICS="1")
    if plane == "socket":
        extra["HOROVOD_TPU_SHM"] = "0"
    run_scenario("elastic_shrink", 4, timeout=120.0, extra_env=extra,
                 expect_rc={3: _SIGKILL_RC})


def test_elastic_resize_mid_overlap():
    """Elastic shrink with the overlap tier armed: the kill lands
    while steady cycles run on the in-flight runner; teardown must
    drain the runner cleanly (no wedged completion thread, no stale
    plan replay) and the shrunk world keeps computing exact
    collectives through a fresh runtime."""
    run_scenario(
        "elastic_shrink", 4, timeout=120.0,
        extra_env=dict(_ELASTIC_ENV,
                       HOROVOD_FAULT_SPEC="rank=3:kill:op=12",
                       HOROVOD_TPU_METRICS="1",
                       HOROVOD_TPU_SHM="0",
                       HOROVOD_OVERLAP_INFLIGHT="2",
                       HOROVOD_OVERLAP_BUCKETS="4"),
        expect_rc={3: _SIGKILL_RC})


def test_elastic_coordinator_death_reelects():
    """SIGKILL rank 0 (coordinator + controller socket): old rank 1
    wins the deterministic election, hosts the new controller, and
    the world continues at ws=2."""
    run_scenario(
        "elastic_coordinator_death", 3, timeout=120.0,
        extra_env=dict(_ELASTIC_ENV,
                       HOROVOD_FAULT_SPEC="rank=0:kill:op=8"),
        expect_rc={0: _SIGKILL_RC})


def test_elastic_double_fault_kill_during_rendezvous():
    """A second rank dies ON ENTRY TO the re-rendezvous barrier
    (fault trigger rdzv=1): the barrier waits out its window for the
    silent victim and still closes with the remaining survivors."""
    run_scenario(
        "elastic_double_fault", 4, timeout=120.0,
        extra_env=dict(
            _ELASTIC_ENV,
            HOROVOD_ELASTIC_WINDOW="4",
            HOROVOD_ELASTIC_MIN_WORLD="2",
            HOROVOD_FAULT_SPEC="rank=3:kill:op=8;rank=2:kill:rdzv=1"),
        expect_rc={2: _SIGKILL_RC, 3: _SIGKILL_RC})


def test_elastic_rejoin_after_shrink():
    """Shrink then GROW: after the kill, old rank 0 respawns the lost
    slot as a joiner (the launcher supervision loop's move); it is
    admitted at the next rendezvous barrier, resyncs the State by
    broadcast, and the world trains to completion at full size."""
    run_scenario(
        "elastic_rejoin", 3, timeout=180.0,
        extra_env=dict(_ELASTIC_ENV,
                       HOROVOD_FAULT_SPEC="rank=2:kill:op=8"),
        expect_rc={2: _SIGKILL_RC})


def test_elastic_disabled_keeps_fail_fast():
    """Without HOROVOD_ELASTIC the wrapper is transparent: the PR 2
    WorldAbortedError (naming the dead rank) propagates verbatim."""
    run_scenario(
        "elastic_disabled_fail_fast", 3, timeout=60.0,
        extra_env={**_HB_ENV,
                   "HOROVOD_FAULT_SPEC": "rank=1:kill:op=3"},
        expect_rc={1: _SIGKILL_RC})


# -- self-operation (HOROVOD_SELFOP=1, common/selfop.py): the --------
# supervision policy acting AHEAD of failure — preemption drain,
# telemetry-driven demotion, and the launcher restart from async
# checkpoints — docs/fault_tolerance.md "Self-operation".


def test_selfop_preempt_drains_before_the_kill():
    """A ``preempt`` fault SIGTERMs rank 3 with a 45s grace window:
    the supervision tick drains it out of the world (clean exit 0 —
    no SIGKILL, no blacklist-worthy death) and the survivors resize
    to ws=3 with zero lost steps, every post-resize collective
    bit-exact vs a fresh shrunk world, the resize attributed to
    the policy."""
    run_scenario(
        "selfop_preempt", 4, timeout=120.0,
        extra_env=dict(
            _ELASTIC_ENV,
            HOROVOD_FAULT_SPEC="rank=3:preempt:cycle=40:seconds=45",
            HOROVOD_PREEMPT_GRACE="45",
            HOROVOD_TPU_METRICS="1"))
    # no expect_rc: the preempted rank MUST exit 0 (clean retirement)


def test_selfop_demote_habitual_straggler():
    """A persistent delay fault makes launch rank 1 the last arriver
    in ~every gather; after the churn cooldown the coordinator demotes
    it to the ring tail via a same-size resize. Every member installs
    the identical world-replicated verdict, non-demoted ranks pace
    their cycle top, and the demoted rank's last-arriver share drops
    below the trigger threshold — the skew improves."""
    run_scenario(
        "selfop_demote", 4, timeout=150.0,
        extra_env=dict(
            _ELASTIC_ENV,
            HOROVOD_FAULT_SPEC=(
                "rank=1:delay:cycle=5:ms=20:count=1000000"),
            HOROVOD_SELFOP_DEMOTE_WINDOW="40",
            # the policy consumes the live telemetry plane: the
            # straggler attribution window only arms with it
            HOROVOD_TPU_METRICS="1"))


def test_selfop_below_min_world_restart_from_checkpoints():
    """SIGKILL two of three ranks at the same step — below the min
    world, nothing to shrink to. The launcher's restart budget
    (HOROVOD_TPU_ELASTIC_RESTARTS / --restarts) starts a FRESH world
    which resumes from the async sharded checkpoints at EXACTLY the
    last committed batch (zero staleness here: the kill lands in an
    idle window after the shards were cut), and the final params are
    bit-identical to a never-killed world's."""
    from horovod_tpu.run.launch import HostBlacklist, run_local_elastic

    with tempfile.TemporaryDirectory() as tmp:
        script = os.path.join(tmp, "train.py")
        with open(script, "w") as f:
            f.write(_SELFOP_RESTART_SCRIPT.format(
                repo=REPO, tmp=tmp, total=30, k=12))
        env = {
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_CYCLE_TIME": "1",
            "HOROVOD_HEARTBEAT_INTERVAL": "0.3",
            "HOROVOD_HEARTBEAT_TIMEOUT": "3",
            "HOROVOD_ELASTIC_WINDOW": "6",
            "HOROVOD_SELFOP_CKPT_DIR": os.path.join(tmp, "ckpt"),
            "HOROVOD_SELFOP_CKPT_INTERVAL": "1",
        }
        rc = run_local_elastic(
            3, [sys.executable, script], env=env, min_np=2,
            blacklist=HostBlacklist(base_s=30.0, retries=0),
            restarts=1)
        assert rc == 0, rc
        for r in (1, 2):
            assert os.path.exists(
                os.path.join(tmp, f"killed.{r}.marker")), \
                "the injected deaths never happened"
        for r in range(3):
            assert os.path.exists(os.path.join(tmp, f"done{r}.ok")), \
                f"rank {r} never finished in the restarted world"


_SELFOP_RESTART_SCRIPT = """\
import faulthandler
import os
import sys
import time

faulthandler.dump_traceback_later(90, exit=True)
sys.path.insert(0, "{repo}")
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.common import elastic

TOTAL = {total}
K = {k}
TMP = "{tmp}"
launch_rank = os.environ.get("HOROVOD_RANK", "")
my_marker = os.path.join(TMP, "killed.%s.marker" % launch_rank)
restarted = os.path.exists(os.path.join(TMP, "killed.1.marker"))

hvd.init()
state = elastic.State(params=np.zeros(16, np.float32), batch=0)


def grad(b, r):
    return np.full(16, float((r + 1) * (b % 7 + 1)), np.float32)


def expected(b, ws):
    return np.full(16, float(sum(range(1, ws + 1)) * (b % 7 + 1)),
                   np.float32)


@elastic.run
def train(state):
    if restarted:
        # the restarted world resumes from the async shards cut in
        # the idle window at batch K — nothing newer was committed
        # before the deaths, so the restore is exact, not just fresh
        assert state.batch == K, state.batch
    while state.batch < TOTAL:
        g = hvd.allreduce(grad(state.batch, hvd.rank()),
                          average=False, name="eg")
        np.testing.assert_array_equal(g, expected(state.batch,
                                                  hvd.size()))
        state.params = state.params + g
        state.batch += 1
        state.commit()
        if state.batch == K:
            # idle across >= 3 checkpoint buckets so every rank
            # persists its shard of the SAME commit seq, then two
            # ranks die at once: ws=1 < min world -> world lost
            time.sleep(3.2)
            if launch_rank in ("1", "2") \\
                    and not os.path.exists(my_marker):
                open(my_marker, "w").close()
                os.kill(os.getpid(), 9)


train(state)
want = np.zeros(16, np.float32)
for b in range(TOTAL):
    want = want + expected(b, hvd.size())
np.testing.assert_array_equal(state.params, want)
open(os.path.join(TMP, "done%s.ok" % hvd.rank()), "w").close()
hvd.shutdown()
"""


def test_rank_subset_init():
    """init(comm=[1, 2]) on 3 processes: the 2-rank subset allreduces
    while the third abstains in a size-1 world."""
    run_scenario("subset_world", 3, timeout=120.0)


@pytest.mark.time_limit(270)
def test_subset_world_hierarchical():
    """A rank-subset sub-world spanning two multi-rank fake hosts
    activates the hierarchical control plane inside the subset."""
    run_scenario(
        "subset_world_hier", 6, timeout=240.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


# -- multi-tenant collective service (docs/multitenancy.md) -----------------

def test_tenants_two_concurrent_exact():
    """Two tenants spanning one ws=4 fleet train concurrently from
    threads; per-tenant results are exact and tenant A's sequence
    replays bit-identically once B goes idle."""
    run_scenario("tenants_exact", 4, timeout=180.0)


def test_tenants_tensor_parallel_plus_data_parallel():
    """A tensor-parallel tenant (row-parallel partial-sum allreduces +
    column-parallel allgathers) and a data-parallel tenant (averaged
    gradient allreduces) share one ws=4 fleet: exact results on every
    step of both, per-lane QoS accounting, and a bit-identical solo
    replay proving co-scheduling never touched the math."""
    run_scenario("tenants_tp_dp", 4, timeout=180.0)


def test_tenants_priority_weights_skew_cycle_share():
    """A 3:1 weighting measurably shifts the contended cycle share
    toward the heavy tenant (with real deferrals on the light lane)."""
    run_scenario("tenants_priority", 2, timeout=180.0)


def test_tenants_quota_defers_over_quota_tenant():
    """A cycles/sec quota paces the capped tenant (deferred, never
    corrupted) while its unlimited co-tenant runs free."""
    run_scenario("tenants_quota", 2, timeout=180.0)


def test_tenants_sigkill_isolated_to_one_tenant():
    """SIGKILL inside tenant A aborts only A's world; disjoint tenant
    B on the same fleet trains to completion, exact."""
    run_scenario("tenants_fault_isolation", 4, timeout=180.0,
                 expect_rc={1: _SIGKILL_RC})


@pytest.mark.time_limit(270)
def test_tenants_service_attach_snapshot_detach():
    """hvdtpurun --service semantics end to end: a 2-rank warm fleet
    serves a 2-replica job that attaches, pulls a parameter snapshot
    via the broadcast fanout, and detaches — no fleet re-rendezvous."""
    gate_port = _free_port()
    run_scenario("tenants_service", 4, timeout=240.0,
                 extra_env={"HOROVOD_TPU_SERVICE": "1",
                            "HOROVOD_TPU_SERVICE_PORT": str(gate_port)})


def test_mxnet_adapter():
    """The MXNet adapter executes end-to-end against the NDArray
    protocol double under a real 2-process world."""
    run_scenario("mxnet", 2, timeout=120.0)


def test_checkpoint_resume(tmp_path_factory):
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        run_scenario("checkpoint_resume", 2,
                     extra_env={"HVD_TEST_CKPT_DIR": tmp})


def test_xla_mesh_backend():
    """Real multi-process JAX CPU world -> XlaMeshBackend data plane."""
    run_scenario("xla_backend", 2, timeout=180.0)


@pytest.mark.time_limit(270)
def test_xla_mesh_backend_tree_broadcast():
    """HOROVOD_XLA_BCAST=tree: the binary-tree ppermute broadcast
    rendering delivers every root's values (3 ranks exercises the
    non-power-of-two round structure)."""
    run_scenario("xla_backend", 3, timeout=240.0,
                 extra_env={"HOROVOD_XLA_BCAST": "tree"})


@pytest.mark.time_limit(270)
def test_xla_async_overlap_end_to_end(tmp_path):
    """Negotiation/execution overlap proven END-TO-END: a deliberately
    slow big XLA collective stays in flight while later cycles
    negotiate and complete small collectives through the real TCP
    gather; rank 0's timeline shows the interleave."""
    run_scenario(
        "xla_async_overlap", 2, timeout=240.0,
        per_rank_env=lambda rank: (
            {"HOROVOD_TIMELINE": str(tmp_path / "overlap.json")}
            if rank == 0 else {}))


@pytest.mark.time_limit(330)
def test_xla_ragged_allgather_skew_guard():
    """1 big / 4 tiny ranks: the fused allgather switches to the
    masked-psum (allgatherv-shaped) rendering; uniform shapes keep the
    padded all_gather."""
    run_scenario("xla_ragged_allgather", 5, timeout=300.0)


def test_xla_hierarchical_allreduce():
    run_scenario("xla_hierarchical", 2, timeout=180.0,
                 extra_env={"HOROVOD_HIERARCHICAL_ALLREDUCE": "1"})


@pytest.mark.time_limit(270)
def test_xla_hierarchical_allreduce_multihost():
    """Forced 2-host topology (4 ranks): hierarchical allreduce must
    compile and run the factored (cross, local) psum with values
    matching the flat path bit-for-bit."""
    run_scenario(
        "xla_hier_allreduce_multihost", 4, timeout=240.0,
        extra_env={"HOROVOD_HIERARCHICAL_ALLREDUCE": "1"},
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


@pytest.mark.time_limit(270)
def test_xla_hierarchical_allgather():
    """Forced 2-host topology (2 ranks per fake host): the
    HOROVOD_HIERARCHICAL_ALLGATHER knob must route allgather through
    the two-level (local, cross) path."""
    run_scenario(
        "xla_hierarchical_allgather", 4, timeout=240.0,
        extra_env={"HOROVOD_HIERARCHICAL_ALLGATHER": "1"},
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})


@pytest.mark.time_limit(330)
def test_coordinator_fuzz_through_hier_controller():
    """The 240-job mixed-collective fuzz with every rank's requests
    riding aggregated frames (3 ranks, 2 fake hosts): randomized
    per-rank submission order must still negotiate to one exact total
    order through the relay tier."""
    run_scenario(
        "coordinator_fuzz", 3, timeout=300.0,
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{min(rank, 1)}"})


def test_hmac_secret_through_hierarchy():
    """One shared HOROVOD_SECRET_KEY across a fake 2-host topology:
    every tier of the hierarchical control plane (coordinator <-> root
    and root <-> leaf channels, native or Python) authenticates frames
    and collectives stay exact."""
    run_scenario(
        "allreduce", 4,
        extra_env={"HOROVOD_SECRET_KEY": "round5-hier-secret"},
        per_rank_env=lambda rank: {
            "HOROVOD_HOSTNAME": f"fakehost{rank // 2}"})
