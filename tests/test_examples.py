"""Every example must actually run (reference model: the examples tree
is part of the tested surface — .travis.yml runs the example scripts'
frameworks' test files; here we execute each example end-to-end with
tiny shapes so a user's first contact with the repo can't be broken).

Each example runs in its own subprocess: examples own their world
(hvd.init/shutdown) and some need a virtual multi-device CPU platform,
which must be configured before jax imports."""

import json
import os
import subprocess
import sys

import pytest

from tests.test_multiprocess import thread_pool_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EX = os.path.join(REPO, "examples")

# _run's own timeout= is the limit that speaks first
pytestmark = pytest.mark.time_limit(450)


def _run(script, *args, n_devices=1, timeout=420, extra_env=None):
    return _python([os.path.join(EX, script), *args], script, n_devices,
                   timeout, extra_env)


# The example's ``__main__`` once for each list of arguments, a line
# between two runs' output.
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


def _run_in_turn(script, *runs, **kwargs):
    """The example once for each of ``runs`` (a list of arguments each),
    in turn in ONE interpreter, which imports the example's framework
    once; each run's stdout. Each run is the example's whole
    ``__main__``, from its ``hvd.init()`` to its ``hvd.shutdown()``."""
    path = os.path.join(EX, script)
    out = _python(["-c", _IN_TURN, path, json.dumps(runs)], script,
                  **kwargs)
    outs = out.split(_NEXT_RUN + "\n")
    assert len(outs) == len(runs), out
    return outs


def _python(argv, script, n_devices=1, timeout=420, extra_env=None):
    env = {**os.environ, **thread_pool_env(1)}
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_CYCLE_TIME"] = "1"
    if extra_env:
        env.update(extra_env)
    flags = env.get("XLA_FLAGS", "")
    # Scrub any inherited device-count flag, then pin ours.
    flags = " ".join(f for f in flags.split()
                     if "host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, *argv],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO)
    assert proc.returncode == 0, (
        f"{script} failed\n--- stdout ---\n{proc.stdout[-3000:]}\n"
        f"--- stderr ---\n{proc.stderr[-3000:]}")
    return proc.stdout


def test_jax_mnist():
    out = _run("jax_mnist.py", "--epochs", "1", "--batch-size", "256")
    assert "loss" in out.lower()


def test_torch_mnist():
    out = _run("torch_mnist.py", "--epochs", "1", "--batch-size", "256")
    assert "loss" in out.lower()


def test_tensorflow_mnist():
    out = _run("tensorflow_mnist.py", "--epochs", "1",
               "--batch-size", "256")
    assert "loss" in out.lower()


def test_keras_mnist():
    out = _run("keras_mnist.py")
    assert "val" in out.lower() or "loss" in out.lower()


@pytest.mark.slow
def test_jax_synthetic_benchmark():
    out = _run("jax_synthetic_benchmark.py", "--batch-size", "2",
               "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
               "--num-iters", "1")
    assert "img/sec" in out.lower()


@pytest.mark.slow
def test_transformer_long_context():
    """Newly green with the jaxshim port; 25s of 8-device CPU-mesh
    compile makes it a wall-clock outlier — the ring-attention paths
    it drives stay tier-1 via test_parallel."""
    out = _run("transformer_long_context.py", "--seq-len", "256",
               "--batch-size", "2", "--layers", "2", "--heads", "2",
               "--head-dim", "16", "--steps", "2", n_devices=8)
    assert "mesh" in out.lower()


@pytest.mark.slow
def test_moe_pipeline_parallel():
    """Newly green with the jaxshim port; ~29s of 8-device CPU-mesh
    compile — the dp x pp x ep Trainer paths stay tier-1 via
    test_parallel's pipelined-LM and expert-sharding tests."""
    out = _run("moe_pipeline_parallel.py", n_devices=8)
    assert "loss" in out.lower() or "moe" in out.lower()


def test_zero_fsdp():
    out = _run("zero_fsdp.py", n_devices=8)
    assert "ZeRO-1" in out and "FSDP" in out


def test_torch_imagenet_resnet50(tmp_path):
    """ImageNet-scale torch example (fp16 allreduce + gradient
    accumulation + warmup + checkpoint/resume), smoke-sized. The
    resume needs a run that starts beside a checkpoint, so the example
    runs twice; one interpreter runs both (torch and jax imported
    once)."""
    ckpt = str(tmp_path / "checkpoint-{epoch}.pth.tar")
    out, resumed = _run_in_turn(
        "torch_imagenet_resnet50.py",
        ["--epochs", "1", "--steps-per-epoch", "1", "--batch-size", "2",
         "--batches-per-allreduce", "2", "--image-size", "32",
         "--num-classes", "10", "--width", "8", "--fp16-allreduce",
         "--checkpoint-format", ckpt],
        # resume path: epoch 1 checkpoint found -> trains epoch 2 only
        ["--epochs", "2", "--steps-per-epoch", "1", "--batch-size", "2",
         "--image-size", "32", "--num-classes", "10", "--width", "8",
         "--checkpoint-format", ckpt])
    assert "loss" in out.lower()
    assert os.path.exists(ckpt.format(epoch=1))
    assert "epoch 2/2" in resumed and "epoch 1/2" not in resumed


@pytest.mark.slow
@pytest.mark.time_limit(630)
def test_keras_imagenet_resnet50(tmp_path):
    """ImageNet-scale keras example: warmup + staged-decay callbacks,
    metric averaging, fusion-threshold sweep knob."""
    out = _run("keras_imagenet_resnet50.py", "--epochs", "1",
               "--steps-per-epoch", "2", "--batch-size", "2",
               "--image-size", "32", "--num-classes", "10",
               "--fusion-threshold", str(1 << 20), "--fp16-allreduce",
               "--checkpoint-dir", str(tmp_path), timeout=600)
    assert "loss" in out.lower()


def test_keras_mnist_advanced():
    """Warmup + LR schedule + MetricAverage composed in one fit."""
    out = _run("keras_mnist_advanced.py", "--epochs", "3",
               "--warmup-epochs", "1", "--batch-size", "128")
    assert "lr trajectory" in out and "val_loss" in out


@pytest.mark.slow
@pytest.mark.time_limit(630)
def test_keras_spark_training():
    """End-to-end Spark workflow in fake-pyspark demo mode: driver
    dataset -> spark.run training -> driver-side scoring."""
    out = _run("keras_spark_training.py", "--num-proc", "2",
               timeout=600, extra_env={"HVD_FAKE_PYSPARK": "1"})
    assert "holdout RMSE" in out


def test_torch_synthetic_benchmark():
    out = _run("torch_synthetic_benchmark.py", "--model",
               "resnet50tiny", "--batch-size", "4",
               "--num-warmup-batches", "1", "--num-batches-per-iter",
               "1", "--num-iters", "2")
    assert "Img/sec per process" in out and "Total img/sec" in out


def test_tensorflow_mnist_eager():
    out = _run("tensorflow_mnist_eager.py", "--steps", "40")
    first, last = out.split("loss ")[-1].split(" over ")[0].split(" -> ")
    assert float(last) < float(first)  # it actually learns


def test_mxnet_mnist():
    out = _run("mxnet_mnist.py", "--steps", "40",
               extra_env={"HVD_FAKE_MXNET": "1"})
    assert "loss" in out and "->" in out


def test_tensorflow_word2vec():
    out = _run("tensorflow_word2vec.py", "--steps", "60")
    assert "IndexedSlices" in out
    first, last = out.split("loss ")[1].split(" over ")[0].split(" -> ")
    assert float(last) < float(first)  # it actually learns


@pytest.mark.parametrize("script", sorted(
    f for f in os.listdir(EX) if f.endswith(".py")))
def test_every_example_is_covered(script):
    """A new example without a smoke test above fails this guard."""
    covered = {
        "jax_mnist.py", "torch_mnist.py", "tensorflow_mnist.py",
        "keras_mnist.py", "jax_synthetic_benchmark.py",
        "transformer_long_context.py", "moe_pipeline_parallel.py",
        "zero_fsdp.py", "tensorflow_word2vec.py",
        "torch_imagenet_resnet50.py", "keras_imagenet_resnet50.py",
        "keras_mnist_advanced.py", "keras_spark_training.py",
        "torch_synthetic_benchmark.py", "tensorflow_mnist_eager.py",
        "mxnet_mnist.py",
    }
    assert script in covered, f"add a smoke test for examples/{script}"
