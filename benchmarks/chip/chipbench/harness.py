"""One rank of one cell: set-up, window, and the check behind it.

Everything a cell is comes from data: its configuration file names a
family (``families/<family>.py``), its traffic file a mode (``injit``
or ``eager``), ranks and batch. This module imports JAX and the
program; ``run.py``, which starts it, does not before it has started
the ranks of a world of more than one.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import time

import flax  # noqa: F401  (here, so that its time is the imports phase's)
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.profiler import TraceAnnotation

import horovod_tpu.jax as hvd
from horovod_tpu import metrics, native, spmd
from horovod_tpu.utils.compile_cache import enable_compile_cache

from chipbench import check, peaks, trace_reduce, weights

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
CHECK_STEPS = 3           # the first steps the reference follows
MIN_TIMED_S = 0.25        # a host-clock reading spans this or more
TRACE_SECONDS = 4.0       # the traced window: a few seconds, no more
PHASES = ("imports", "world_start", "device_open", "state_init",
          "compile_or_load", "warmup")


def say(msg: str) -> None:
    print(f"chipbench: {msg}", flush=True)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` beside the harness, by its file: names may
    hold dots, which an import statement could not spell."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve_cell(manifest: dict, workload: str, rehearse: bool) -> dict:
    """The cell's entry, configuration and traffic; in a rehearsal the
    files' ``rehearse`` overrides are laid over their sizes."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     f"{cell['traffic']}.json"))
    if rehearse:
        for data in (config, traffic):
            over = data.pop("rehearse", {})
            assumed = over.pop("assumed", {})
            data.update(over)
            data.setdefault("assumed", {}).update(assumed)
    if traffic["chips"] != cell["chips"]:
        raise ValueError(f"{workload}: BENCHMARK.json says {cell['chips']} "
                         f"chips, the traffic file {traffic['chips']}")
    return {"cell": cell, "config": config, "traffic": traffic}


class Clock:
    """Seconds of each set-up phase, from the wall-clock instant the
    command started."""

    def __init__(self, t0: float):
        self.t0 = self.last = t0
        self.phases: dict = {}

    def mark(self, name: str) -> None:
        now = time.time()
        self.phases[name] = self.phases.get(name, 0.0) + now - self.last
        self.last = now


class CompileCounter:
    """Calls to the backend's compile-or-load, and how many of them the
    persistent cache served (a hit is a load, a miss a compilation), by
    the part of the run they fell in: the window has none at all, and a
    warm run's set-up no miss."""

    EVENTS = {"/jax/compilation_cache/cache_misses": "cache_misses",
              "/jax/compilation_cache/cache_hits": "cache_hits"}
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.part = "setup"
        self.counts: dict = {}
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _bump(self, what):
        part = self.counts.setdefault(self.part, {})
        part[what] = part.get(what, 0) + 1

    def _event(self, event, **kw):
        if event in self.EVENTS:
            self._bump(self.EVENTS[event])

    def _duration(self, event, duration, **kw):
        if event == self.COMPILE:
            self._bump("compile_calls")


# -- the program, built from the cell's data -------------------------------

class Program:
    """The compiled step with its state: the one object set-up builds,
    warms up and hands to the window. ``mode`` ``injit`` is the jitted
    step of ``train_steps`` over a mesh of this process's chips;
    ``eager`` is upstream's hook-then-synchronize flow: a jitted
    backward, ``hvd.allreduce_gradients`` on the device arrays, a
    jitted apply."""

    def __init__(self, spec: dict, seed: int, size: int, param_dtype=None,
                 control_leaves: str = ""):
        self.seed, self.size = seed, size
        self.config, self.traffic = spec["config"], spec["traffic"]
        self.mode = self.traffic["mode"]
        self.family = load_module("families", self.config["family"])
        self.sz = self.family.sizes(
            self.config, self.config["assumed"]["per_chip_batch"])
        # the control: parameters kept in ``param_dtype``, all of them or
        # the leaves whose path holds ``control_leaves``
        self.param_dtype, self.control_leaves = param_dtype, control_leaves
        opt = self.config["assumed"]["optimizer"]
        if opt["name"] != "sgd_momentum":
            raise ValueError(f"optimizer {opt['name']!r}: the check "
                             f"follows SGD with momentum and no other")
        self.lr, self.momentum = opt["learning_rate"], opt["momentum"]
        self.shapes, self.fan_ins = self.family.param_shapes(self.sz)
        self.model = self.family.build_model(self.sz)
        self._check_shapes()
        devices = jax.local_devices()
        if self.mode == "injit":
            self.chips = self.traffic["chips"]
            self.mesh = spmd.create_mesh(
                {"data": self.chips}, devices=devices[:self.chips])
            self.state_sharding = spmd.replicated_sharding(self.mesh)
            self.batch_sharding = spmd.batch_sharding(self.mesh)
            self.tx = hvd.DistributedOptimizer(
                optax.sgd(self.lr, momentum=self.momentum), axis="data")
        elif self.mode == "eager":
            self.chips = 1
            self.mesh = None
            self.state_sharding = self.batch_sharding = None
            self.tx = optax.sgd(self.lr, momentum=self.momentum)
        else:
            raise ValueError(f"traffic mode {self.mode!r}")
        self.rows = self.sz["per_chip_batch"] * self.chips
        self.samples_per_step = (self.rows * size
                                 * self.family.samples_per_row(self.sz))
        self.compiled: dict = {}

    def _check_shapes(self):
        """The program's own parameter tree must be the one the family
        file describes, name for name: the reference is built on it."""
        theirs = self.family.program_shapes(self.model, self.sz)
        flat = {"/".join(str(getattr(k, "key", k)) for k in path):
                tuple(leaf.shape) for path, leaf in
                jax.tree_util.tree_flatten_with_path(theirs)[0]}
        if flat != weights.flat_shapes(self.shapes):
            ours = weights.flat_shapes(self.shapes)
            odd = sorted(set(flat.items()) ^ set(ours.items()))[:6]
            raise ValueError(f"the program's parameters are not the "
                             f"family file's: {odd}")

    # state and batch, from the seed ----------------------------------------
    def make_model_state(self, sharding=None) -> dict:
        """``{"params", "aux"}`` in float32 from the seed: the same for
        every rank, for the reference, and every time it is called."""
        state = weights.make_tree(self.shapes, self.fan_ins, self.seed,
                                  stream=0, sharding=sharding)
        state.setdefault("aux", {})     # a model without running statistics
        return state

    def make_batch(self, rank: int, sharding=None) -> tuple:
        """Rank ``rank``'s batch: another stream of the seed a rank."""
        fn = self.family.make_batch(self.sz, self.rows)
        return jax.jit(fn, out_shardings=sharding)(
            weights.seed_key(self.seed, stream=1 + rank))

    def make_state(self) -> dict:
        """The training state as a user's start-up makes it: the
        parameters broadcast from rank 0 through the runtime, the
        optimizer's state made from them."""
        state = self.make_model_state(self.state_sharding)
        if self.param_dtype is not None:
            state["params"] = jax.tree_util.tree_map_with_path(
                lambda path, p: p.astype(self.param_dtype)
                if self.control_leaves in jax.tree_util.keystr(path) else p,
                state["params"])
        state["params"] = hvd.broadcast_parameters(state["params"],
                                                   root_rank=0)
        state["opt"] = jax.jit(
            self.tx.init, out_shardings=self.state_sharding)(
                state["params"])
        return jax.block_until_ready(state)

    # programs ----------------------------------------------------------
    def compile(self, state: dict, batch: tuple) -> None:
        if self.mode == "injit":
            step = self.family.injit_step(self.model, self.tx, self.mesh)
            self.compiled["step"] = step.lower(
                *self.family.injit_args(state, batch)).compile()
            return
        loss_fn = self.family.program_loss(self.model)

        def backward(params, aux, *batch):
            (loss, new_aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, aux, *batch)
            return loss, new_aux, grads

        def apply(params, opt, grads):
            updates, opt = self.tx.update(grads, opt, params)
            return optax.apply_updates(params, updates), opt

        self.compiled["backward"] = jax.jit(backward).lower(
            state["params"], state["aux"], *batch).compile()
        grads = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
            state["params"])
        self.compiled["apply"] = jax.jit(
            apply, donate_argnums=(0, 1)).lower(
                state["params"], state["opt"], grads).compile()

    def memory_need_bytes(self) -> dict:
        """The compiler's count for the window's executables: the
        largest single program's arguments + temporaries + code, its
        donated arguments counted once."""
        need = {}
        for name, exe in self.compiled.items():
            ma = exe.memory_analysis()
            need[name] = {
                "arguments": int(ma.argument_size_in_bytes),
                "outputs": int(ma.output_size_in_bytes),
                "aliased": int(ma.alias_size_in_bytes),
                "temporaries": int(ma.temp_size_in_bytes),
                "code": int(ma.generated_code_size_in_bytes)}
        return need

    def step(self, state: dict, batch: tuple, stop: float = 0.0):
        """One training step through the compiled programs:
        ``(state, loss, stop)``. In an eager world of more than one
        rank ``stop`` rides the gradients' exchange as one more scalar
        and comes back as the ranks' mean, so that every rank leaves
        the window after the same step."""
        if self.mode == "injit":
            out = self.compiled["step"](*self.family.injit_args(state, batch))
            state, loss = self.family.injit_unpack(out, state)
            return state, loss, stop
        with TraceAnnotation("bench.backward"):
            loss, aux, grads = self.compiled["backward"](
                state["params"], state["aux"], *batch)
        with TraceAnnotation("bench.exchange"):
            tree = {"grads": grads}
            if self.size > 1:       # a world of one needs no vote
                tree["stop"] = np.float32(stop)
            out = hvd.allreduce_gradients(
                tree, compression=hvd.Compression.fp16
                if self.traffic.get("fp16_allreduce")
                else hvd.Compression.none)
            if self.size > 1:
                stop = float(out["stop"])
        with TraceAnnotation("bench.apply"):
            params, opt = self.compiled["apply"](
                state["params"], state["opt"], out["grads"])
        return {"params": params, "aux": aux, "opt": opt}, loss, stop

    def last_output(self, state: dict, loss):
        """What a step's last program wrote: ready when the step is.
        (Waiting on every leaf of the state costs the host a
        millisecond or two a step.)"""
        if self.mode == "injit":
            return loss
        return jax.tree_util.tree_leaves(state["params"])[-1], loss

    def momentum_of(self, state: dict):
        """The optimizer's momentum: the leaves of its state, which for
        SGD with momentum are the trace and nothing else."""
        leaves = jax.tree_util.tree_leaves(state["opt"])
        n = len(jax.tree_util.tree_leaves(state["params"]))
        if len(leaves) != n:
            raise ValueError(f"optimizer state has {len(leaves)} leaves "
                             f"for {n} parameters: not a plain momentum")
        return leaves

    def first_steps(self, state: dict, batch: tuple) -> dict:
        """Drive ``state`` through the first steps and read what the
        check compares. Consumes ``state``."""
        losses, grad_norms = [], None
        for i in range(CHECK_STEPS):
            state, loss, _ = self.step(state, batch)
            losses.append(float(loss))
            if i == 0:
                grad_norms = check.leaf_norms(self.momentum_of(state))
        start = self.make_model_state(self.state_sharding)
        update_norms = check.diff_norms(
            {"params": state["params"], "aux": state["aux"]}, start)
        return {"losses": losses, "grad_norms": grad_norms,
                "update_norms": update_norms, "state": state}

    def reference(self) -> dict:
        """The plain reference's first steps, over every rank's batch.
        An in-jit step over several chips reports the mean loss of all
        its rows and applies the mean gradient: the reference takes
        the rows a chip's share at a time (the whole batch in float32
        does not fit one chip beside the parameters, and the mean over
        rows is linear), which holds for a model whose rows do not meet
        before the loss."""
        shards = [self.make_batch(r) for r in range(self.size)]
        whole = self.mode == "injit" and self.chips > 1
        if whole:
            if self.shapes["aux"]:
                raise ValueError(
                    "statistics over all the rows of a mesh (batch norm "
                    "over the data axis) cannot be followed a chip's "
                    "share at a time: the reference has no such path yet")
            n = self.sz["per_chip_batch"]
            shards = [tuple(a[i * n:(i + 1) * n] for a in shards[0])
                      for i in range(self.chips)]
        return check.reference_steps(
            self.family.reference_stages(self.sz), self.make_model_state,
            shards, self.lr, self.momentum, CHECK_STEPS, mean_loss=whole)


# -- one rank's run ---------------------------------------------------------

def device_line(traffic: dict, rehearse: bool) -> dict:
    """The devices of this process, which must hold the mix's chips: a
    rank of a launched world holds one, a single process all of them."""
    chips = 1 if traffic["ranks"] > 1 else traffic["chips"]
    devices = jax.local_devices()
    if not rehearse and devices[0].platform != "tpu":
        sys.exit(f"chipbench: found no TPU (platform "
                 f"{devices[0].platform!r}); the benchmark measures "
                 f"the chip and does not fall back")
    if len(devices) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chip(s) in this "
                 f"process, found {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def registry_snapshot() -> dict:
    view = metrics()
    return view["local"] if view["enabled"] else {}


def run_window(program: Program, state: dict, batch: tuple, seconds: float,
               group: int):
    """Steps in timed groups of ``group`` until ``seconds`` have passed;
    every step of the window is in a group. The host runs as a
    training loop does, without waiting for a step before it sends the
    next. An in-jit group's time runs from the end of the group before
    it to its own end on the device: the host waits for a group's last
    loss only once the next group is sent, so the device never waits
    for the host's clock. An eager step waits for its own gradients
    inside the exchange, so a group's time is the host's. The window
    ends when the last step's last output is ready. Returns the state,
    the groups' seconds, the first and last losses and the window's
    length."""
    times, losses = [], []
    pending, stop = None, 0.0
    with TraceAnnotation("bench.window"):
        start = last = time.perf_counter()

        def close_group():
            nonlocal last
            now = time.perf_counter()
            times.append(now - last)
            last = now

        while stop == 0.0:
            with TraceAnnotation("bench.step"):
                for i in range(group):
                    # Decided before a group's last step is sent, so
                    # that in a world it can ride that step's exchange.
                    over = (i == group - 1
                            and time.perf_counter() - start >= seconds)
                    state, loss, stop = program.step(
                        state, batch, 1.0 if over else 0.0)
            losses.append(loss)
            if program.mode == "eager":
                close_group()
            elif pending is not None:
                jax.block_until_ready(pending)
                close_group()
            pending = loss
        jax.block_until_ready(program.last_output(state, loss))
        if program.mode == "eager":
            times[-1] += time.perf_counter() - last   # the last apply
        else:
            close_group()
        window = time.perf_counter() - start
    return state, times, [float(l) for l in (losses[0], losses[-1])], window


def run_rank(args, manifest: dict) -> dict:
    """Run one rank of the cell and return its result; prints the
    earlier lines as it goes."""
    clock = Clock(args.t0)
    if args.launched is not None:
        # a rank of a launched world: the launcher's time is the world's
        clock.phases["world_start"] = args.launched - args.t0
        clock.last = args.launched
    spec = resolve_cell(manifest, args.workload, args.rehearse)
    traced = bool(args.trace)
    if traced:
        os.environ["HOROVOD_TPU_METRICS"] = "1"     # read by hvd.init()
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    built = os.path.exists(os.path.join(ROOT, "native", "libhvdtpu.so"))
    loaded, reason = native.build_status()
    clock.mark("imports")

    traffic = spec["traffic"]
    ranks = traffic["ranks"]
    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    if size != ranks:
        sys.exit(f"chipbench: the traffic file says {ranks} rank(s), the "
                 f"world has {size}")
    clock.mark("world_start")

    device = device_line(traffic, args.rehearse)
    jax.block_until_ready(jnp.zeros(8) + 1)
    if size > 1:
        hvd.barrier()       # until every rank has its chip
    clock.mark("device_open")
    quiet = rank != 0
    if not quiet:
        say(f"cell {args.workload} seed {args.seed} trace {args.trace} "
            f"device {device} compile cache {cache_dir}")
        say(f"native core: loaded={loaded} "
            f"{'found built' if built else 'built by this run'} {reason}")

    program = Program(spec, args.seed, size)
    state = program.make_state()
    batch = jax.block_until_ready(
        program.make_batch(rank, program.batch_sharding))
    clock.mark("state_init")

    program.compile(state, batch)
    clock.mark("compile_or_load")

    # Warm-up is the program's first steps, through the window's own
    # call; their losses are what the check replays afterwards.
    warm_losses, warm_times = [], []
    for _ in range(CHECK_STEPS):
        t = time.perf_counter()
        state, loss, _ = program.step(state, batch)
        warm_losses.append(float(loss))
        warm_times.append(time.perf_counter() - t)
    group = max(1, math.ceil(MIN_TIMED_S / min(warm_times[1:])))
    if program.mode == "eager" and size > 1:
        # every rank must time the same groups
        group = int(round(float(hvd.allreduce(
            jnp.float32(group), average=False, name="bench.group")) / size))
    clock.mark("warmup")

    before = registry_snapshot()
    seconds = min(args.seconds, TRACE_SECONDS) if traced else args.seconds
    trace_dir = os.path.join(ROOT, ".bench_run", args.workload,
                             f"trace{rank}")
    # A rehearsal has no device to trace: its readers see no trace.
    profiled = traced and not args.rehearse
    if profiled:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    setup_s = time.time() - clock.t0      # command start to first step
    counter.part = "window"
    state, times, window_losses, window_s = run_window(
        program, state, batch, seconds, group)
    counter.part = "after"
    if profiled:
        jax.profiler.stop_trace()
    after = registry_snapshot()
    steps = len(times) * group
    runtime_peak = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
                       if d.memory_stats() else 0
                       for d in jax.local_devices()[:program.chips])
    need = program.memory_need_bytes()
    need_bytes = max(n["arguments"] + n["outputs"] - n["aliased"]
                     + n["temporaries"] + n["code"] for n in need.values())

    # What every rank's parameters are after the window, as a few sums
    # the parent compares: identical parameters give identical sums.
    digest = [float(x) for x in jax.jit(lambda p: [
        jnp.sum(jnp.abs(l)) for l in jax.tree_util.tree_leaves(p)])(
            state["params"])]
    del state

    # -- the check, behind the window --------------------------------------
    t_check = time.perf_counter()
    replay = program.first_steps(program.make_state(), batch)
    del replay["state"]
    # The world ends here, every rank at once: the reference that rank 0
    # follows next is no collective, and nobody waits for it.
    if size > 1:
        hvd.barrier()
    hvd.shutdown()
    result = {"rank": rank, "device": device, "digest": digest}
    checks = {}
    if rank == 0:
        reference = program.reference()
        checks = check.compare(replay, reference,
                               spec["config"]["check"]["limits"])
        for k in range(CHECK_STEPS):
            say(f"check: step {k + 1} loss {replay['losses'][k]!r} "
                f"reference {reference['losses'][k]!r}")
    gap = max(abs(a - b) for a, b in zip(warm_losses, replay["losses"]))
    checks["replay_loss_gap"] = {
        "value": gap, "limit": 0.0, "ok": gap == 0.0}
    finite = all(math.isfinite(l) for l in window_losses + warm_losses)
    falling = finite and window_losses[1] < warm_losses[0]
    checks["window_loss_falls"] = {
        "value": window_losses[1] - warm_losses[0] if finite else math.inf,
        "limit": 0.0, "ok": falling}
    check_s = time.perf_counter() - t_check
    for name, c in checks.items():
        say(f"check: rank {rank} {name} {c['value']!r} limit "
            f"{c['limit']!r} {'ok' if c['ok'] else 'NOT OK'}")
    result["checks"] = checks
    result["correct"] = all(c["ok"] for c in checks.values())

    if not quiet:
        say("phases: " + " ".join(
            f"{p}={clock.phases.get(p, 0.0):.3f}" for p in PHASES)
            + f" setup_s={setup_s:.3f}")
        say(f"compilations and cache traffic by part: "
            f"{json.dumps(counter.counts, sort_keys=True)}")
        say(f"memory: peak_bytes_in_use {runtime_peak} (before the "
            f"reference ran); compiler's memory_analysis "
            f"{json.dumps(need, sort_keys=True)}; need {need_bytes}")
        say(f"window: {steps} steps in {len(times)} timed groups of "
            f"{group} over {window_s:.4f} s, "
            f"{program.samples_per_step} {program.family.SAMPLE} a step; "
            f"warm-up losses {warm_losses}, window's first and last "
            f"{window_losses}; check took {check_s:.1f} s")
    in_window = counter.counts.get("window", {})
    if in_window.get("compile_calls") or in_window.get("cache_misses"):
        say(f"NOT OK: the window compiled: {in_window}")
        result["correct"] = False

    # -- metrics ---------------------------------------------------------------
    peak = None if args.rehearse else peaks.chip_peak(device["kind"])
    rate = steps * program.samples_per_step / window_s \
        / (size * program.chips)
    step_ms = [1e3 * t / group for t in times]
    ctx = {"spec": spec, "sz": program.sz, "family": program.family,
           "peak": peak, "rate": rate, "steps": steps, "group": group,
           "step_ms": step_ms, "window_s": window_s, "phases": clock.phases,
           "setup_s": setup_s, "need_bytes": need_bytes,
           "registry": registry_delta(before, after),
           "size": size, "chips": program.chips, "trace": None, "notes": []}
    if profiled:
        ctx["trace"] = trace_reduce.reduce_trace(trace_reduce.load_xplane(
            trace_reduce.find_xplane(trace_dir)))
        result["busy_s"] = ctx["trace"]["busy_s"]
        result["traced_window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": ctx["trace"]["device_ops"],
            "idle_gaps": ctx["trace"]["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["metrics"] = read_metrics(manifest, args.workload, traced, ctx)
    for note in ctx["notes"]:
        say(note)
    if args.rehearse and traced and not quiet:
        listed = [m for m in manifest["per_layer"]
                  if args.workload in m.get("workloads", [args.workload])]
        say(f"rehearsal: per-layer readers {len(listed)} listed for the "
            f"cell, {len(result['metrics'])} gave a value")
    if not quiet and ctx["registry"]:
        served = {k: v for k, v in ctx["registry"].items()
                  if k.startswith("hvd_backend_ops_total") and v}
        say(f"backends that served the window: {served}")
    result["memory_peak_bytes"] = max(runtime_peak, need_bytes)
    result["attempted"] = steps
    result["failed"] = 0 if finite else steps
    return result


def registry_delta(before: dict, after: dict) -> dict:
    """Counters' and histograms' growth over the window: ``{name:
    value}`` for counters, ``{name: {"sum", "count"}}`` for
    histograms; gauges as they stand after it."""
    out = {}
    for name, rec in after.items():
        old = before.get(name, {})
        if "counts" in rec:
            out[name] = {"sum": rec["sum"] - old.get("sum", 0.0),
                         "count": rec["count"] - old.get("count", 0)}
        elif rec.get("k") == "counter":
            out[name] = rec["v"] - old.get("v", 0)
        else:
            out[name] = rec.get("v")
    return out


def read_metrics(manifest: dict, workload: str, traced: bool,
                 ctx: dict) -> dict:
    """The cell's metrics by their names in BENCHMARK.json: end to end
    from the harness's own clock in a plain run, per layer from each
    metric's reader file in a traced one. A reader that finds nothing
    to read returns ``None`` and the metric is left out."""
    out = {}
    for m in manifest["per_layer" if traced else "end_to_end"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        kind = "layer_metrics" if traced else "end_to_end"
        value = load_module(kind, m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
