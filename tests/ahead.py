"""Interpreters of their own, started beside the in-process tests.

An example's smoke run or a plain world of ranks shares nothing with
the pytest process that waits for it and asserts on what it printed
alone, no timing: seconds in which this process would sit in ``wait()``
while six of the run's eight cores stand idle. The session starts such
runs from its first moment, two at a time (``tests/conftest.py`` asks
each file that has some, for the tests that were selected), and each
test takes its run's result; a run that was not started ahead (a test
picked alone by ``-k`` after collection, a program that imports the
file) is made by the test that asks, as ever.

A whole file of in-process tests that needs nothing of the session's
process (a kernel's cases, a model against its references: Python that
traces, one core) is such a run too: ``FileRun`` is ``pytest`` on the
file's selected tests in an interpreter of its own, which writes every
test's reports where this process reads them, in the tests' order, and
reports them as its own when their turn comes (``tests/conftest.py``:
``pytest_runtest_protocol``).

Not for a world whose assertions read the clock (faults, stalls,
heartbeats, stragglers): those run alone, when their test does. What is
started here has the minutes of ``tests/chip_bench`` to run beside, and
the first test after them that runs in the session's process waits
(``wait``) until all of it is through."""

import concurrent.futures
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

_AT_ONCE = 2
_pool = None
_started: dict = {}
_running: set = set()


def start(key, run, *args):
    """``run(*args)`` on the pool, to be taken under ``key``."""
    global _pool
    if _pool is None:
        _pool = concurrent.futures.ThreadPoolExecutor(
            _AT_ONCE, thread_name_prefix="ahead")
    if key not in _started:
        _started[key] = _pool.submit(run, *args)


def take(key, run, *args):
    """What ``run(*args)`` returns (or raises): from the run started
    ahead under ``key`` if there is one, from a run of its own if not."""
    future = _started.get(key)
    if future is None or future.cancelled():
        return run(*args)
    return future.result()


def communicate(proc: subprocess.Popen, timeout: float):
    """``proc.communicate(timeout=timeout)``, the process killed at the
    limit as ``subprocess.run`` kills it, and killed by ``stop`` if the
    session ends while it runs."""
    _running.add(proc)
    try:
        return proc.communicate(timeout=timeout)
    except BaseException:
        proc.kill()
        proc.wait()
        raise
    finally:
        _running.discard(proc)


def wait():
    """Until nothing started here still runs."""
    concurrent.futures.wait(list(_started.values()))


def stop():
    """The session's end: nothing started here outlives it."""
    for future in _started.values():
        future.cancel()
    for proc in list(_running):
        proc.kill()
    _started.clear()


# -- a file's tests in an interpreter of their own ---------------------------

# The variable that tells a run of pytest where to write its tests'
# reports (and that it is such a run: it starts nothing ahead itself).
REPORTS_TO = "TESTS_REPORTS_TO"


def write_report(config, report):
    """In the interpreter that runs a file: one line a report, as
    pytest serialises it for a process that did not make it."""
    with open(os.environ[REPORTS_TO], "a") as f:
        f.write(json.dumps(config.hook.pytest_report_to_serializable(
            config=config, report=report)) + "\n")


class FileRun:
    """``pytest`` on ``nodeids`` (one file's selected tests) in an
    interpreter of its own; ``reports(item)`` are that test's
    reports (set-up, call, teardown) as soon as they are
    written, or one failure that says what the interpreter said if it
    ended without them."""

    _QUIET_S = 900.0    # no report for so long: the interpreter is ended

    def __init__(self, config, path, nodeids):
        self.config, self.path, self.nodeids = config, path, nodeids
        self.dir = tempfile.mkdtemp(prefix="tests-of-their-own.")
        self._reports_at = os.path.join(self.dir, "reports.jsonl")
        self._read, self._by_test, self.seconds = 0, {}, None
        self._started = concurrent.futures.Future()

    def run(self):
        """On the pool: the interpreter from its start to its end."""
        open(self._reports_at, "w").close()
        with open(os.path.join(self.dir, "said"), "w") as said:
            # from where this session was started and with its plugins
            # (``-p``), each test by its path, so that the names agree
            config = self.config
            proc = subprocess.Popen(
                [sys.executable, "-m", "pytest", "-q",
                 *(arg for plugin in config.option.plugins
                   for arg in ("-p", plugin)),
                 *(os.path.join(config.rootpath, nodeid)
                   for nodeid in self.nodeids)],
                cwd=str(config.invocation_params.dir), stdout=said,
                stderr=subprocess.STDOUT,
                env={**os.environ, REPORTS_TO: self._reports_at})
            self._started.set_result(proc)
            t0 = time.monotonic()
            communicate(proc, None)
            self.seconds = time.monotonic() - t0

    def forget(self):
        shutil.rmtree(self.dir, ignore_errors=True)

    def _read_new(self):
        with open(self._reports_at, "rb") as f:
            f.seek(self._read)
            while (line := f.readline()).endswith(b"\n"):
                self._read += len(line)
                report = self.config.hook.pytest_report_from_serializable(
                    config=self.config, data=json.loads(line))
                self._by_test.setdefault(report.nodeid, []).append(report)

    def _complete(self, nodeid):
        self._read_new()
        got = self._by_test.get(nodeid, ())
        return got if any(r.when == "teardown" for r in got) else None

    def reports(self, item):
        proc = self._started.result()
        last_news, read = time.monotonic(), self._read
        while not (got := self._complete(item.nodeid)):
            if proc.poll() is not None:
                got = self._complete(item.nodeid) \
                    or [self._no_report(item, f"ended ({proc.returncode})")]
                break
            if self._read != read:
                last_news, read = time.monotonic(), self._read
            elif time.monotonic() - last_news > self._QUIET_S:
                proc.kill()
                got = [self._no_report(item, "was ended: no test's report "
                                       f"in {self._QUIET_S:g} s")]
                break
            time.sleep(0.05)
        return got

    def _no_report(self, item, why):
        from _pytest.reports import TestReport
        # behind a margin: no line of it is taken for a line of this run
        with open(os.path.join(self.dir, "said"), errors="replace") as f:
            said = "".join("  | " + line for line in
                           f.read()[-6000:].splitlines(keepends=True))
        return TestReport(
            item.nodeid, item.location, dict.fromkeys(item.keywords, 1),
            "failed",
            f"the interpreter that ran {self.path} {why} without this "
            f"test's report; it said:\n{said}", "call")
