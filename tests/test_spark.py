"""Spark integration tests (reference: test/test_spark.py:51-107 —
local-mode run asserting per-rank results and env, plus graceful
failure without the launcher dependency). Real pyspark is absent from
the image, so partitions run in forked worker processes via
tests/fake_pyspark — the same process shape Spark local mode gives the
integration (see that module's docstring)."""

import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RUN_SCRIPT = r"""
import sys
sys.path.insert(0, {repo!r})
from tests import fake_pyspark
fake_pyspark.install()

import numpy as np
import horovod_tpu.spark


def train():
    import os
    import numpy as np
    import horovod_tpu as hvd
    rank, size = hvd.rank(), hvd.size()
    out = hvd.allreduce(np.full(8, float(rank + 1), np.float32),
                        average=False, name="spark.ar")
    assert np.allclose(out, sum(range(1, size + 1))), out[0]
    return {{"rank": rank, "size": size,
             "env_rank": os.environ["HOROVOD_RANK"],
             "sum0": float(out[0])}}


results = horovod_tpu.spark.run(train, num_proc=3)
assert [r["rank"] for r in results] == [0, 1, 2], results
assert all(r["size"] == 3 for r in results)
assert all(r["env_rank"] == str(r["rank"]) for r in results)
assert all(r["sum0"] == 6.0 for r in results)
print("SPARK_OK")
"""


def test_spark_run_local_mode():
    """3 ranks through horovod_tpu.spark.run: rendezvous, coordinator
    socket handoff, per-rank env, allreduce, rank-ordered results."""
    script = _RUN_SCRIPT.format(repo=REPO)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, timeout=120)
    assert out.returncode == 0, out.stdout.decode() + out.stderr.decode()
    assert b"SPARK_OK" in out.stdout


def test_spark_requires_pyspark():
    """Graceful failure without pyspark (reference analog: mpirun
    missing from PATH, test/test_spark.py:91-107)."""
    import horovod_tpu.spark as hspark
    with pytest.raises(ImportError, match="requires pyspark"):
        hspark.run(lambda: None, num_proc=1)


def test_parent_death_watchdog_kills_orphan():
    """An intermediary process starts a grandchild running the
    watchdog; killing the intermediary must make the grandchild exit
    (reference: spark/task/mpirun_exec_fn.py:26-38)."""
    script = (
        "import os, sys, time\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "pid = os.fork()\n"
        "if pid == 0:\n"
        "    from horovod_tpu.spark import _start_parent_watchdog\n"
        "    _start_parent_watchdog(poll_s=0.2)\n"
        "    print('CHILD', os.getpid(), flush=True)\n"
        "    time.sleep(60)\n"
        "    os._exit(0)\n"
        "print('PARENT', pid, flush=True)\n"
        "time.sleep(60)\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.Popen([sys.executable, "-c", script], env=env,
                         stdout=subprocess.PIPE)
    child_pid = None
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and child_pid is None:
        line = p.stdout.readline().decode().strip()
        if line.startswith("CHILD"):
            child_pid = int(line.split()[1])
    assert child_pid is not None
    p.kill()  # kill the intermediary -> grandchild is orphaned
    p.wait()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            os.kill(child_pid, 0)  # still alive?
        except ProcessLookupError:
            return  # watchdog fired
        time.sleep(0.2)
    os.kill(child_pid, signal.SIGKILL)
    raise AssertionError("orphaned grandchild outlived its parent")
