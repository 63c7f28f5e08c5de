"""Every example must actually run (reference model: the examples tree
is part of the tested surface — .travis.yml runs the example scripts'
frameworks' test files; here we execute each example end-to-end with
tiny shapes so a user's first contact with the repo can't be broken).

Each example runs in its own subprocess: examples own their world
(hvd.init/shutdown) and some need a virtual multi-device CPU platform,
which must be configured before jax imports. Such a run shares nothing
with the process that waits for it, so the session starts the selected
tests' runs from its first moment, two at a time, beside the in-process
tests (``tests/ahead.py``; ``EXAMPLES`` below is what it starts, in this
order: those that import jax alone first, while the frameworks'
bytecode is still being written), and a test takes its example's
result, or runs the example itself if nobody started it."""

import dataclasses
import json
import os
import subprocess
import sys
import tempfile

import pytest

from tests import ahead
from tests.test_multiprocess import thread_pool_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EX = os.path.join(REPO, "examples")

# an example's own timeout= is the limit that speaks first
pytestmark = pytest.mark.time_limit(450)


@dataclasses.dataclass(frozen=True)
class Example:
    """``examples/<name>.py`` once for each list of arguments in
    ``runs``, in turn in ONE interpreter (which imports the example's
    framework once); ``{dir}`` in an argument is a directory made for
    the runs."""
    runs: tuple
    n_devices: int = 1
    timeout: int = 420
    extra_env: tuple = ()


def _once(*args, **kwargs):
    return Example((args,), **kwargs)


# By the name of the script, which is the name of its test.
EXAMPLES = {
    "jax_mnist": _once("--epochs", "1", "--batch-size", "256"),
    "zero_fsdp": _once(n_devices=8),
    "mxnet_mnist": _once("--steps", "40",
                         extra_env=(("HVD_FAKE_MXNET", "1"),)),
    "torch_mnist": _once("--epochs", "1", "--batch-size", "256"),
    "torch_synthetic_benchmark": _once(
        "--model", "resnet50tiny", "--batch-size", "4",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "2"),
    "torch_imagenet_resnet50": Example((
        ("--epochs", "1", "--steps-per-epoch", "1", "--batch-size", "2",
         "--batches-per-allreduce", "2", "--image-size", "32",
         "--num-classes", "10", "--width", "8", "--fp16-allreduce",
         "--checkpoint-format", "{dir}/checkpoint-{{epoch}}.pth.tar"),
        # resume path: epoch 1 checkpoint found -> trains epoch 2 only
        ("--epochs", "2", "--steps-per-epoch", "1", "--batch-size", "2",
         "--image-size", "32", "--num-classes", "10", "--width", "8",
         "--checkpoint-format", "{dir}/checkpoint-{{epoch}}.pth.tar"))),
    "tensorflow_mnist": _once("--epochs", "1", "--batch-size", "256"),
    "keras_mnist": _once(),
    "keras_mnist_advanced": _once("--epochs", "3", "--warmup-epochs", "1",
                                  "--batch-size", "128"),
    "tensorflow_mnist_eager": _once("--steps", "40"),
    "tensorflow_word2vec": _once("--steps", "60"),
    # the slow tier's
    "jax_synthetic_benchmark": _once(
        "--batch-size", "2", "--num-warmup-batches", "1",
        "--num-batches-per-iter", "1", "--num-iters", "1"),
    "transformer_long_context": _once(
        "--seq-len", "256", "--batch-size", "2", "--layers", "2",
        "--heads", "2", "--head-dim", "16", "--steps", "2", n_devices=8),
    "moe_pipeline_parallel": _once(n_devices=8),
    "keras_imagenet_resnet50": _once(
        "--epochs", "1", "--steps-per-epoch", "2", "--batch-size", "2",
        "--image-size", "32", "--num-classes", "10",
        "--fusion-threshold", str(1 << 20), "--fp16-allreduce",
        "--checkpoint-dir", "{dir}", timeout=600),
    "keras_spark_training": _once(
        "--num-proc", "2", timeout=600,
        extra_env=(("HVD_FAKE_PYSPARK", "1"),)),
}


def start_ahead(items):
    """The session's start (``tests/conftest.py``): the examples of the
    selected tests begin, in ``EXAMPLES``' order."""
    tests = {item.originalname for item in items}
    for name in EXAMPLES:
        if "test_" + name in tests:
            ahead.start(("example", name), run_example, name)


def example(name):
    """``(the stdout of each of the example's runs, their directory)``:
    of the run the session started ahead, or of one made now."""
    return ahead.take(("example", name), run_example, name)


def printed(name):
    """The stdout of an example that runs once."""
    (out,), _ = example(name)
    return out


# The example's ``__main__`` once for each list of arguments, a line
# between two runs' output. Each run is the example's whole
# ``__main__``, from its ``hvd.init()`` to its ``hvd.shutdown()``.
_NEXT_RUN = "-- the example again --"
_IN_TURN = f"""
import json, runpy, sys
script, runs = sys.argv[1], json.loads(sys.argv[2])
for n, argv in enumerate(runs):
    if n:
        print({_NEXT_RUN!r}, flush=True)
    sys.argv = [script, *argv]
    runpy.run_path(script, run_name="__main__")
"""


def run_example(name):
    spec, script = EXAMPLES[name], name + ".py"
    directory = tempfile.mkdtemp(prefix=f"example-{name}.") \
        if any("{dir}" in arg for run in spec.runs for arg in run) else None
    runs = [[arg.format(dir=directory) for arg in run] for run in spec.runs]
    path = os.path.join(EX, script)
    argv = [path, *runs[0]] if len(runs) == 1 \
        else ["-c", _IN_TURN, path, json.dumps(runs)]
    out = _python(argv, script, spec.n_devices, spec.timeout,
                  dict(spec.extra_env))
    outs = out.split(_NEXT_RUN + "\n")
    assert len(outs) == len(runs), out
    return outs, directory


def _python(argv, script, n_devices, timeout, extra_env):
    env = {**os.environ, **thread_pool_env(1)}
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_CYCLE_TIME"] = "1"
    env.update(extra_env)
    flags = env.get("XLA_FLAGS", "")
    # Scrub any inherited device-count flag, then pin ours.
    flags = " ".join(f for f in flags.split()
                     if "host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, *argv], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, env=env, cwd=REPO)
    stdout, stderr = ahead.communicate(proc, timeout)
    assert proc.returncode == 0, (
        f"{script} failed\n--- stdout ---\n{stdout[-3000:]}\n"
        f"--- stderr ---\n{stderr[-3000:]}")
    return stdout


def test_jax_mnist():
    out = printed("jax_mnist")
    assert "loss" in out.lower()


def test_torch_mnist():
    out = printed("torch_mnist")
    assert "loss" in out.lower()


def test_tensorflow_mnist():
    out = printed("tensorflow_mnist")
    assert "loss" in out.lower()


def test_keras_mnist():
    out = printed("keras_mnist")
    assert "val" in out.lower() or "loss" in out.lower()


@pytest.mark.slow
def test_jax_synthetic_benchmark():
    out = printed("jax_synthetic_benchmark")
    assert "img/sec" in out.lower()


@pytest.mark.slow
def test_transformer_long_context():
    """Newly green with the jaxshim port; 25s of 8-device CPU-mesh
    compile makes it a wall-clock outlier — the ring-attention paths
    it drives stay tier-1 via test_parallel."""
    out = printed("transformer_long_context")
    assert "mesh" in out.lower()


@pytest.mark.slow
def test_moe_pipeline_parallel():
    """Newly green with the jaxshim port; ~29s of 8-device CPU-mesh
    compile — the dp x pp x ep Trainer paths stay tier-1 via
    test_parallel's pipelined-LM and expert-sharding tests."""
    out = printed("moe_pipeline_parallel")
    assert "loss" in out.lower() or "moe" in out.lower()


def test_zero_fsdp():
    out = printed("zero_fsdp")
    assert "ZeRO-1" in out and "FSDP" in out


def test_torch_imagenet_resnet50():
    """ImageNet-scale torch example (fp16 allreduce + gradient
    accumulation + warmup + checkpoint/resume), smoke-sized. The
    resume needs a run that starts beside a checkpoint, so the example
    runs twice; one interpreter runs both (torch and jax imported
    once)."""
    (out, resumed), directory = example("torch_imagenet_resnet50")
    assert "loss" in out.lower()
    assert os.path.exists(os.path.join(directory, "checkpoint-1.pth.tar"))
    assert "epoch 2/2" in resumed and "epoch 1/2" not in resumed


@pytest.mark.slow
@pytest.mark.time_limit(630)
def test_keras_imagenet_resnet50():
    """ImageNet-scale keras example: warmup + staged-decay callbacks,
    metric averaging, fusion-threshold sweep knob."""
    out = printed("keras_imagenet_resnet50")
    assert "loss" in out.lower()


def test_keras_mnist_advanced():
    """Warmup + LR schedule + MetricAverage composed in one fit."""
    out = printed("keras_mnist_advanced")
    assert "lr trajectory" in out and "val_loss" in out


@pytest.mark.slow
@pytest.mark.time_limit(630)
def test_keras_spark_training():
    """End-to-end Spark workflow in fake-pyspark demo mode: driver
    dataset -> spark.run training -> driver-side scoring."""
    out = printed("keras_spark_training")
    assert "holdout RMSE" in out


def test_torch_synthetic_benchmark():
    out = printed("torch_synthetic_benchmark")
    assert "Img/sec per process" in out and "Total img/sec" in out


def test_tensorflow_mnist_eager():
    out = printed("tensorflow_mnist_eager")
    first, last = out.split("loss ")[-1].split(" over ")[0].split(" -> ")
    assert float(last) < float(first)  # it actually learns


def test_mxnet_mnist():
    out = printed("mxnet_mnist")
    assert "loss" in out and "->" in out


def test_tensorflow_word2vec():
    out = printed("tensorflow_word2vec")
    assert "IndexedSlices" in out
    first, last = out.split("loss ")[1].split(" over ")[0].split(" -> ")
    assert float(last) < float(first)  # it actually learns


@pytest.mark.parametrize("script", sorted(
    f for f in os.listdir(EX) if f.endswith(".py")))
def test_every_example_is_covered(script):
    """A new example without a smoke test above fails this guard."""
    name = script[:-len(".py")]
    assert name in EXAMPLES and callable(globals().get("test_" + name)), \
        f"add a smoke test for examples/{script}"
