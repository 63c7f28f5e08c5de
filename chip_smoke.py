"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one chip, one process
    python chip_smoke.py --chips 4  # only what exists across chips

It drives the main path through the entry points a user calls and
checks what comes out. The first failure raises and the exit code is
non-zero; nothing is caught and turned into a string. Without a TPU it
fails at once: a CPU run of this script proves nothing about the chip.

One chip (the default), in order:

* *device*: versions, ``device_kind``, compile-cache directory, and the
  native core rebuilt from ``native/hvdtpu.cc`` (a library found in the
  tree is never trusted);
* *eager negotiated path*: device-resident arrays through
  ``hvd.allreduce`` / ``broadcast`` / ``allgather`` /
  ``grouped_allreduce`` — named tensors, negotiation, fusion, a backend
  — checked against numpy;
* *transformer trainer at full width*: the L12 d2048 S2048 B4 bf16
  program of ``bench.py`` (``horovod_tpu.models.train_steps``): finite
  and falling losses, the flash kernels present in the executable, the
  first loss against the dense f32-softmax reference, and whether
  ``block_until_ready`` really waits on this platform;
* *ResNet-50 trainer*: batch 256 at 224x224, cross-replica batch norm.

``--chips 4`` runs no one-chip phase: first the launcher's world of
four ranks, each on its own chip, while this process has not touched
JAX (a chip belongs to one process); then, after that world has
exited, the in-jit transformer step over a 4-chip mesh against the
one-device program fed the four shards in turn.

Timings printed here are smoke timings, not measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

SEED = 0
LM_SEQ = 2048
LM_LAYERS = 12
LM_PER_CHIP_BATCH = 4
LM_STEPS = 4
SYNC_STEPS = 5          # per ending, in the block_until_ready check
RESNET_PER_CHIP_BATCH = 256
RESNET_STEPS = 3
WORLD_TIMEOUT_S = 600


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# -- device ---------------------------------------------------------------

def native_core() -> None:
    """Build the native core from source and load it; with a compiler
    present a failure is fatal (the rule of tests/conftest.py)."""
    from horovod_tpu import native
    native.rebuild()
    loaded, reason = native.build_status()
    say(f"native core: loaded={loaded} {reason}".rstrip())
    if not loaded and native.compiler_available() \
            and not native.disabled_via_env():
        raise RuntimeError(f"native core build failed: {reason}")


def require_tpu(count: int):
    """``jax.devices()``, which must be at least ``count`` TPU chips."""
    from importlib import metadata

    import jax
    import jaxlib

    devices = jax.devices()
    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {metadata.version('libtpu')}")
    say(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}")
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: found no TPU (jax.devices()[0].platform="
                 f"{devices[0].platform!r}); this script proves the chip "
                 f"path and does not fall back")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} chip(s), found "
                 f"{len(devices)}")
    return devices


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def memory_line(compiled, device) -> str:
    """The runtime's peak beside the compiler's own count: on this
    platform ``peak_bytes_in_use`` leaves a program's temporaries out,
    so neither alone says what the step needs."""
    ma = compiled.memory_analysis()
    return (f"peak_bytes_in_use {peak_bytes(device)} (process so far); "
            f"compiler's memory_analysis: arguments "
            f"{ma.argument_size_in_bytes} temporaries "
            f"{ma.temp_size_in_bytes} code "
            f"{ma.generated_code_size_in_bytes} bytes")


# -- eager negotiated path ------------------------------------------------

def eager_collectives(hvd) -> None:
    """Rank-dependent device arrays through the background runtime,
    exact against numpy at any world size. Values are small integers
    held in f32 so every sum and mean is exact."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rank, size = hvd.rank(), hvd.size()
    keys = iter(jax.random.split(jax.random.key(SEED), 64))

    def ints(shape):
        """(this rank's device array, the rank-0 values on the host)."""
        base = jax.random.randint(next(keys), shape, -1000, 1000,
                                  jnp.int32).astype(jnp.float32)
        return base + rank, np.asarray(base)

    rank_sum = size * (size - 1) / 2

    x, base = ints((1024, 1024))
    got = hvd.allreduce(x, average=False, name="smoke.sum")
    np.testing.assert_array_equal(np.asarray(got), size * base + rank_sum)
    got = hvd.allreduce(x, average=True, name="smoke.avg")
    np.testing.assert_array_equal(
        np.asarray(got), (size * base + rank_sum) / size)

    root = min(2, size - 1)
    got = hvd.broadcast(x, root_rank=root, name="smoke.bcast")
    np.testing.assert_array_equal(np.asarray(got), base + root)

    g, gbase = ints((8, 384))
    got = hvd.allgather(g, name="smoke.gather")
    np.testing.assert_array_equal(
        np.asarray(got),
        np.concatenate([gbase + r for r in range(size)]))

    shapes = [(17,), (1024,), (256, 256), (3, 3, 64, 64), (2048, 33),
              (5,)] * 6
    group, bases = zip(*(ints(s) for s in shapes))
    outs = hvd.grouped_allreduce(list(group), average=False,
                                 name="smoke.group")
    for out, b in zip(outs, bases):
        np.testing.assert_array_equal(np.asarray(out),
                                      size * b + rank_sum)
    mib = sum(int(np.prod(s)) for s in shapes) * 4 / 2**20
    say(f"rank {rank}/{size}: allreduce sum+avg, broadcast root {root}, "
        f"allgather, grouped_allreduce of {len(shapes)} tensors "
        f"({mib:.1f} MiB) exact against numpy")

    from horovod_tpu import metrics
    served = {name: rec["v"]
              for name, rec in metrics()["local"].items()
              if name.startswith("hvd_backend_ops_total") and rec["v"]}
    say(f"rank {rank}/{size}: backends that served them: {served}")
    if not served:
        raise RuntimeError("no backend op counter moved: the eager "
                           "path did not go through the runtime")


# -- trainers -------------------------------------------------------------

def lm_setup(mesh):
    """(model, tx, tokens) of the transformer cell on ``mesh``."""
    from horovod_tpu.models import train_steps
    model = train_steps.bench_lm(seq=LM_SEQ, num_layers=LM_LAYERS)
    tokens = train_steps.synthetic_tokens(
        SEED, LM_PER_CHIP_BATCH * mesh.devices.size, LM_SEQ,
        model.cfg.vocab_size, mesh)
    return model, train_steps.distributed_sgd(), tokens


def compile_timed(jitted, *args):
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def flash_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def transformer_phase(mesh) -> None:
    import jax
    import numpy as np

    from horovod_tpu.models import train_steps
    from horovod_tpu.models.transformer import causal_attention

    device = mesh.devices.flat[0]
    model, tx, tokens = lm_setup(mesh)
    params, opt_state = train_steps.lm_train_state(
        model, tx, mesh, tokens, SEED)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))

    # The dense f32-softmax reference on the same parameters and batch,
    # before the step donates them.
    dense = train_steps.bench_lm(seq=LM_SEQ, num_layers=LM_LAYERS,
                                 attention_fn=causal_attention)
    dense_loss = float(jax.jit(train_steps.lm_loss_fn(dense))(
        params, tokens))

    train, compile_s = compile_timed(
        train_steps.lm_train_step(model, tx, mesh),
        params, opt_state, tokens)
    n_calls = flash_calls(train)
    say(f"transformer L{LM_LAYERS} d{model.cfg.embed_dim} S{LM_SEQ} "
        f"B{tokens.shape[0]} {n_params / 1e6:.1f}M params: compiled in "
        f"{compile_s:.1f} s, {n_calls} tpu_custom_call")
    if n_calls != 3 * LM_LAYERS:
        raise RuntimeError(
            f"expected {3 * LM_LAYERS} flash kernels (fwd, dq, dk/dv per "
            f"layer) in the executable, found {n_calls}: interpret mode "
            f"or the dense fallback ran")

    losses = []
    t0 = time.perf_counter()
    for _ in range(LM_STEPS):
        params, opt_state, loss = train(params, opt_state, tokens)
        losses.append(float(loss))
    step_s = (time.perf_counter() - t0) / LM_STEPS
    say(f"transformer losses {losses} ({step_s:.3f} s/step smoke timing, "
        f"first step included)")
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall on the repeated batch: "
                           f"{losses}")
    say(f"flash step loss {losses[0]:.5f} vs dense reference "
        f"{dense_loss:.5f}")
    np.testing.assert_allclose(losses[0], dense_loss, rtol=1e-2)

    # Does block_until_ready wait for the device here? The same N steps
    # ended by it and ended by a value fetch must take the same time.
    def run(end):
        nonlocal params, opt_state
        t0 = time.perf_counter()
        for _ in range(SYNC_STEPS):
            params, opt_state, loss = train(params, opt_state, tokens)
        end(loss)
        return (time.perf_counter() - t0) / SYNC_STEPS
    ready_s = run(jax.block_until_ready)
    fetch_s = run(float)
    say(f"block_until_ready check over {SYNC_STEPS} steps: "
        f"{ready_s:.4f} s/step ended by block_until_ready, "
        f"{fetch_s:.4f} s/step ended by a value fetch")
    if ready_s < 0.5 * fetch_s:
        raise RuntimeError("block_until_ready returned before the "
                           "device finished")
    say(f"transformer {memory_line(train, device)}")


def resnet_phase(mesh) -> None:
    import numpy as np

    from horovod_tpu.models import train_steps

    device = mesh.devices.flat[0]
    model = train_steps.bench_resnet()
    tx = train_steps.distributed_sgd()
    images, labels = train_steps.synthetic_images(
        SEED, RESNET_PER_CHIP_BATCH * mesh.devices.size, mesh)
    params, batch_stats, opt_state = train_steps.resnet_train_state(
        model, tx, mesh, images, SEED)
    train, compile_s = compile_timed(
        train_steps.resnet_train_step(model, tx, mesh),
        params, batch_stats, opt_state, images, labels)
    losses = []
    t0 = time.perf_counter()
    for _ in range(RESNET_STEPS):
        params, batch_stats, opt_state, loss = train(
            params, batch_stats, opt_state, images, labels)
        losses.append(float(loss))
    step_s = (time.perf_counter() - t0) / RESNET_STEPS
    say(f"resnet50 B{images.shape[0]} {images.shape[1]}x{images.shape[2]}"
        f": compiled in {compile_s:.1f} s, losses {losses} ({step_s:.3f} "
        f"s/step smoke timing, first step included)")
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite loss: {losses}")
    say(f"resnet50 {memory_line(train, device)}")


# -- four chips -----------------------------------------------------------

def open_chips() -> list:
    """The numbers of the chip device nodes this process holds open."""
    from horovod_tpu.run.chips import CHIP_NODE
    numbers = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        m = CHIP_NODE.fullmatch(target)
        if m:
            numbers.add(int(m.group(1)))
    return sorted(numbers)


def world_rank() -> None:
    """One rank of the launcher's world: exactly one chip, and not a
    chip any other rank holds."""
    import jax
    import numpy as np

    import horovod_tpu.jax as hvd

    devices = jax.local_devices()
    if devices[0].platform != "tpu" or len(devices) != 1:
        sys.exit(f"chip_smoke: a launcher rank must see exactly one TPU "
                 f"chip, sees {devices}")
    hvd.init()
    jax.block_until_ready(jax.numpy.zeros(8) + 1)  # the chip is open
    chips = open_chips()
    say(f"rank {hvd.rank()}/{hvd.size()}: device {devices[0]} "
        f"TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')} "
        f"holds chip node(s) {chips}")
    if len(chips) != 1:
        sys.exit(f"chip_smoke: a rank must hold one chip node open, "
                 f"holds {chips}")
    held = np.asarray(hvd.allgather(np.array(chips, np.int32),
                                    name="smoke.chips"))
    if len(set(held.tolist())) != hvd.size():
        sys.exit(f"chip_smoke: ranks share chips: {held.tolist()}")
    eager_collectives(hvd)
    hvd.shutdown()


def launcher_world(n: int) -> None:
    """``python -m horovod_tpu.run -np n python chip_smoke.py
    --world-rank``, the README's quick start, in its own session so
    that nothing it started outlives a failure."""
    here = os.path.dirname(os.path.abspath(__file__))
    cmd = [sys.executable, "-m", "horovod_tpu.run", "-np", str(n),
           sys.executable, os.path.abspath(__file__), "--world-rank"]
    say("launcher world: " + " ".join(cmd))
    proc = subprocess.Popen(cmd, cwd=here, start_new_session=True)
    try:
        rc = proc.wait(timeout=WORLD_TIMEOUT_S)
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)  # no-op once all exited
        except ProcessLookupError:
            pass
    if rc != 0:
        sys.exit(f"chip_smoke: the launcher world exited with {rc}")
    say(f"launcher world of {n} ranks: ok")


def injit_phase(devices) -> None:
    """The transformer step over a ``data`` mesh of every chip against
    the one-device program fed the shards in turn."""
    import jax
    import numpy as np

    from horovod_tpu import spmd
    from horovod_tpu.models import train_steps

    n = len(devices)
    last = f"block_{LM_LAYERS - 1}"

    def named(params):
        return {"embed": np.asarray(params["embed"]["embedding"]),
                "down": np.asarray(
                    params[last]["mlp"]["down"]["kernel"])}

    mesh = spmd.create_mesh({"data": n}, devices=devices)
    model, tx, tokens = lm_setup(mesh)
    host_tokens = np.asarray(tokens)
    params, opt_state = train_steps.lm_train_state(
        model, tx, mesh, tokens, SEED)
    start = named(params)
    train, compile_s = compile_timed(
        train_steps.lm_train_step(model, tx, mesh),
        params, opt_state, tokens)
    if "all-reduce" not in train.as_text():
        raise RuntimeError(f"no all-reduce in the {n}-chip executable")
    params, opt_state, loss = train(params, opt_state, tokens)
    mesh_loss = float(loss)
    for leaf in jax.tree_util.tree_leaves(params):
        on = {s.device for s in leaf.addressable_shards}
        if len(leaf.addressable_shards) != n or on != set(devices):
            raise RuntimeError(f"a parameter leaf is not on {n} chips: "
                               f"{leaf.sharding}")
    mesh_update = {k: v - start[k] for k, v in named(params).items()}
    say(f"{n}-chip step: compiled in {compile_s:.1f} s with all-reduce, "
        f"{flash_calls(train)} tpu_custom_call, loss {mesh_loss:.5f}, "
        f"every parameter leaf on {n} chips")
    # Drop the mesh program's state before the one-device program
    # takes device 0.
    del params, opt_state, train, loss

    one = spmd.create_mesh({"data": 1}, devices=devices[:1])
    train = None
    losses, updates = [], []
    for shard in np.split(host_tokens, n):
        shard = jax.device_put(shard, spmd.batch_sharding(one))
        params, opt_state = train_steps.lm_train_state(
            model, tx, one, shard, SEED)
        if train is None:
            for k, v in named(params).items():
                np.testing.assert_array_equal(v, start[k])
            train = train_steps.lm_train_step(model, tx, one).lower(
                params, opt_state, shard).compile()
        params, opt_state, loss = train(params, opt_state, shard)
        losses.append(float(loss))
        updates.append({k: v - start[k]
                        for k, v in named(params).items()})
        del params, opt_state
    say(f"one-device program on the {n} shards: losses {losses}, "
        f"mean {np.mean(losses):.5f}")
    np.testing.assert_allclose(np.mean(losses), mesh_loss, rtol=1e-2)
    for k in mesh_update:
        mean = np.mean([u[k] for u in updates], axis=0)
        err = np.linalg.norm(mean - mesh_update[k]) \
            / np.linalg.norm(mesh_update[k])
        say(f"update of {k}: |mean of {n} one-device updates - {n}-chip "
            f"update| / |{n}-chip update| = {err:.2e}")
        if not err < 2e-2:
            raise RuntimeError(f"{k} update disagrees: {err}")
    say(f"{n}-chip peak_bytes_in_use "
        f"{[peak_bytes(d) for d in devices]} (process so far)")


# -- main -----------------------------------------------------------------

def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--world-rank", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    os.environ["HOROVOD_TPU_METRICS"] = "1"
    if args.world_rank:
        world_rank()
        return

    from horovod_tpu.utils.compile_cache import enable_compile_cache
    say(f"compile cache: {enable_compile_cache()}")
    native_core()
    if args.chips > 1:
        launcher_world(args.chips)  # before this process touches JAX

    devices = require_tpu(args.chips)
    import horovod_tpu.jax as hvd
    from horovod_tpu import spmd
    hvd.init()
    if args.chips > 1:
        injit_phase(devices)
    else:
        eager_collectives(hvd)
        mesh = spmd.create_mesh({"data": 1}, devices=devices[:1])
        transformer_phase(mesh)
        resnet_phase(mesh)
    hvd.shutdown()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind, "count": len(devices)}}),
        flush=True)


if __name__ == "__main__":
    main()
