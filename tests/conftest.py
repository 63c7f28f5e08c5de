"""Test configuration.

Force JAX onto a virtual 8-device CPU platform *before* jax is imported
anywhere, so SPMD/mesh tests exercise real multi-device sharding without
TPU hardware (the driver separately dry-runs the multi-chip path; see
__graft_entry__.py)."""

import faulthandler
import gc
import os
import signal
import sys
import tempfile
import time
from collections import defaultdict

# No source is compiled twice in a run either. The image's site-packages
# carry no bytecode and its environment says PYTHONDONTWRITEBYTECODE=1:
# left so, each of the run's hundreds of interpreters compiles what it
# imports from source (1.4 of the 2.0 s of ``import jax``, 4.5 of the
# 10 s of ``import tensorflow``, half of ``import horovod_tpu``). One
# bytecode cache for this process and everything spawned from it, beside
# ``.jax_cache`` and ignored like it (with a prefix set Python writes no
# ``__pycache__`` anywhere else).
sys.pycache_prefix = os.environ.setdefault(
    "PYTHONPYCACHEPREFIX",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".pycache"))
os.environ.pop("PYTHONDONTWRITEBYTECODE", None)
sys.dont_write_bytecode = False

# This process lives for a quarter of an hour and keeps what it makes
# (every test's report, jax's tracing caches, the compiled programs:
# 2.7 million objects by the end). Python's cyclic collector, at its
# default of a young collection every 700 allocations, ran 21,538 times
# in a run of the suite and took 38 s of it walking them. Tracing
# allocates by the hundred thousand, so the collector comes when that
# many are new.
gc.set_threshold(100_000, 20, 20)

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# What the suite holds the program to on the CPU is its arithmetic
# against references on toy shapes, in code that runs for milliseconds:
# not worth LLVM's optimiser. Spawned ranks and examples inherit the
# variable. The compiles for a described TPU keep the compiler whole
# (the ``whole_compiler`` fixture below).
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")
# No program is compiled twice in a run: the persistent cache below keeps
# every compilation, not only those over jax's default floor of 1 s (most
# of this suite's are under it, the more so without the optimiser), so a
# later test or a spawned rank that builds the same program loads it.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

# The flight recorder (common/trace.py) is ON by default and dumps
# into HOROVOD_TPU_FLIGHT_DIR (default: CWD) on every world abort.
# test_multiprocess._base_env already points SPAWNED worlds at a
# throwaway dir, but IN-PROCESS aborts (e.g. test_timeline driving
# WorldAbortedError through Runtime directly) dump from this very
# process — without a default here each such test leaves a pid-unique
# hvd-flight-*.jsonl in the checkout. setdefault keeps any operator-
# or test-provided dir authoritative.
os.environ.setdefault("HOROVOD_TPU_FLIGHT_DIR",
                      tempfile.mkdtemp(prefix="hvd-flight-conftest."))

# Share one persistent XLA compilation cache across the whole run —
# including every SPAWNED rank and example subprocess (they inherit
# os.environ) — and across runs: the mp tier pays the same model jits
# hundreds of times in short-lived interpreters. The directory is the
# operator's JAX_COMPILATION_CACHE_DIR or the fixed <checkout>/.jax_cache
# (a temporary name would never hit twice).
from horovod_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import pytest  # noqa: E402

# Something may already have imported jax, in which case the env var
# above is too late; jax.config still wins as long as no backend has
# been initialized. (Guarded: the core runtime is importable without
# jax, and the numpy-only tests must stay runnable on jax-less hosts.)
try:
    import jax  # noqa: E402

    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass


# Modules whose tests spawn real worker processes (TCP worlds, example
# smoke runs, launchers): the expensive integration tier. Everything
# else is the fast in-process tier (reference precedent: the
# single-process vs mpirun suite split, .travis.yml:109-122).
_MP_MODULES = {
    "test_multiprocess", "test_examples", "test_launcher",
    "test_spark", "test_autotune_mp", "test_timeline",
}


def pytest_configure(config):
    global _config
    _config = config
    # Build the native core ONCE up front (the zero-copy data plane
    # rides it): with a compiler present a broken build must fail the
    # tier LOUDLY — a silent skip would unhook every native test (and
    # the whole zero-copy plane) from CI forever. Without a compiler
    # the native tests skip with a reason, as before.
    from horovod_tpu import native as _native

    loaded, reason = _native.build_status()
    if not loaded and _native.compiler_available() \
            and not _native.disabled_via_env():
        raise pytest.UsageError(
            f"native core build failed with a compiler present "
            f"({reason}) — fix native/hvdtpu.cc or the Makefile; "
            f"tier-1 refuses to silently drop the zero-copy plane")

    config.addinivalue_line(
        "markers", "mp: spawns worker subprocesses (slow integration "
        "tier; deselect with -m 'not mp' for the ~2-minute fast "
        "suite)")
    config.addinivalue_line(
        "markers", "fast: in-process unit tier (alias: -m fast == "
        "-m 'not mp')")
    config.addinivalue_line(
        "markers", "lint: pure-static hvdlint analyzer checks + "
        "lockdep units (no world spawn; subset of the fast tier — "
        "run alone with -m lint)")
    config.addinivalue_line(
        "markers", "slow: wall-clock outliers (many-world convergence "
        "runs, big example smokes) excluded from the budgeted tier-1 "
        "sweep (-m 'not slow'); the full matrix (plain `pytest "
        "tests/`) still runs them")
    config.addinivalue_line(
        "markers", "interpreter_of_its_own: a file of in-process tests "
        "that needs nothing of the session's process; in a session with "
        "other files its tests run beside them, in an interpreter of "
        "their own (tests/ahead.py)")
    config.addinivalue_line(
        "markers", "time_limit(seconds): this test's own limit in place "
        f"of the default {TEST_LIMIT_S:g} s, for a test whose spawned "
        "worlds carry a longer timeout= of their own")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__.rsplit(".", 1)[-1] in _MP_MODULES:
            item.add_marker(pytest.mark.mp)
        else:
            item.add_marker(pytest.mark.fast)


@pytest.fixture()
def hvd_world():
    """A fresh size-1 horovod_tpu world per test."""
    import horovod_tpu as hvd
    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture(scope="module")
def whole_compiler():
    """The optimiser back on while a module compiles for a described
    TPU: what such a test asserts on (the schedule, what fits VMEM) is
    the compiler's own work."""
    import jax
    was = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", was)


# -- every test has a limit of its own --------------------------------------

TEST_LIMIT_S = 180.0
# The clock the whole suite has: the `timeout` of the command the driver
# runs after every PR (`commands` in /root/TESTS_LAST_RUN.json). A run
# cut there counts only as far as it got, so a run says how much of it
# its tests used (ROADMAP.md T1 has the budget).
SUITE_LIMIT_S = 1470.0
_RUN_STARTED = time.monotonic()     # collection is on the clock too


def _all_stacks() -> str:
    with tempfile.TemporaryFile("w+") as fh:
        faulthandler.dump_traceback(file=fh, all_threads=True)
        fh.seek(0)
        return fh.read()


@pytest.fixture(autouse=True)
def _time_limit(request):
    """Fail (not hang) a test that outlasts its limit, with every
    thread's stack, and let the run go on. ``time_limit(seconds)``
    marks the few whose spawned worlds carry a longer ``timeout=``."""
    marker = request.node.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TEST_LIMIT_S

    def overrun(signum, frame):
        pytest.fail(f"{request.node.nodeid} exceeded its time limit of "
                    f"{limit:g} s\n{_all_stacks()}", pytrace=False)

    was = signal.signal(signal.SIGALRM, overrun)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, was)


# -- a cut run says so ------------------------------------------------------

@pytest.fixture(scope="session", autouse=True)
def _sigterm_as_found():
    """``hvd.init()`` puts the program's preemption handler on SIGTERM
    for the process's life: it waits out HOROVOD_PREEMPT_GRACE (30 s) and
    exits 0, so under it `timeout` running out reads as a kill ten
    seconds later (137, not 124). Spawned ranks install it as ever; this
    process tells ``selfop`` it already has, and so keeps SIGTERM's
    disposition as it found it, inside a test that holds a world too."""
    from horovod_tpu.common import selfop
    selfop._handler_installed = True
    yield
    selfop._handler_installed = False


# -- the frameworks' bytecode is written beside the first tests --------------

@pytest.fixture(scope="session", autouse=True)
def _frameworks_bytecode():
    """On a fresh tree the first interpreter to import TensorFlow, Keras
    or torch compiles some thousands of modules from source and writes
    them to the run's bytecode cache (10 to 25 s of one core, in the
    middle of ``test_adapters.py`` or twice at once in a world's two
    ranks). The whole run has minutes of other tests before it needs
    them, so an interpreter of its own imports them at the start, beside
    those tests; with the cache already written nothing is started."""
    import importlib.util
    import subprocess
    cold = [name for name in ("tensorflow", "keras", "torch")
            if (spec := importlib.util.find_spec(name)) and spec.origin
            and not os.path.exists(importlib.util.cache_from_source(
                spec.origin))]
    from tests import ahead
    if not cold or ahead.REPORTS_TO in os.environ:  # the session's to do
        yield
        return
    proc = subprocess.Popen(
        [sys.executable, "-c", "import " + ", ".join(cold)],
        env={**os.environ, "KERAS_BACKEND": "tensorflow"},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    yield
    proc.kill()
    proc.wait()


# -- a file's whole programs compile beside its first tests ------------------

@pytest.fixture(scope="module", autouse=True)
def _programs_from_the_files_start(request):
    """A file's ``programs`` (a module fixture that lowers its whole
    programs and hands them to ``tests/compiled.py``'s pool) is made at
    the file's start if a selected test of the file takes it: the pool
    then compiles beside the tests that stand before that one."""
    if any(item.module is request.module and "programs" in item.fixturenames
           for item in request.session.items):
        request.getfixturevalue("programs")


# -- interpreters of their own run beside the in-process tests ---------------

# A file of in-process tests that needs nothing of this process, and
# that nothing here needs, says so (``pytestmark``:
# ``interpreter_of_its_own``): a kernel's cases in interpreter mode, a
# model against its references, meshes of virtual devices, the analyzer
# over the tree. Each is Python that traces for most of its seconds, on
# one core of eight. In a session with other files to run, such a
# file's selected tests run in an interpreter of their own from the
# session's start (``tests/ahead.py``), and each is reported here, in
# its turn, by the reports that interpreter wrote for it. Alone
# (``pytest tests/test_kda.py``), under ``-s`` or under ``--pdb`` the
# file runs here as ever.
_file_runs: dict = {}       # a test file's path -> its ``ahead.FileRun``
_config = None


def _file_of(item) -> str:
    return item.nodeid.split("::", 1)[0]


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtestloop(session):
    """What a selected test would only wait for starts now, two at a
    time, in the files' order (``tests/ahead.py``): the files that run
    in an interpreter of their own, the examples' smoke runs and the
    plain worlds of ranks (a file that has such runs has a
    ``start_ahead(its selected tests)``)."""
    from tests import ahead
    option = session.config.option
    if ahead.REPORTS_TO in os.environ or option.collectonly:
        return (yield)      # another session's file, or no run at all
    by_file = defaultdict(list)
    for item in session.items:
        by_file[_file_of(item)].append(item)
    own = len(by_file) > 1 and option.capture != "no" and not option.usepdb
    for path, items in by_file.items():
        if own and items[0].get_closest_marker("interpreter_of_its_own"):
            run = _file_runs[path] = ahead.FileRun(
                session.config, path, [item.nodeid for item in items])
            ahead.start(("file", path), run.run)
        elif hasattr(items[0].module, "start_ahead"):
            items[0].module.start_ahead(items)
    try:
        return (yield)
    finally:
        ahead.stop()
        for run in _file_runs.values():
            run.forget()


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_protocol(item, nextitem):
    """A test of a file that runs in an interpreter of its own: the
    reports that interpreter wrote for it, in this test's turn; its
    seconds here are those this process waited for them. Any other
    test outside ``tests/chip_bench`` first waits until nothing started
    ahead still runs: what is started ahead has the minutes of
    ``tests/chip_bench`` (in-process, no clock in its assertions) to run
    beside, and a world whose assertions read the clock runs alone, as
    ever."""
    from tests import ahead
    run = _file_runs.get(_file_of(item))
    if run is None:
        if not item.nodeid.startswith("tests/chip_bench/"):
            ahead.wait()
        return None
    item.ihook.pytest_runtest_logstart(nodeid=item.nodeid,
                                       location=item.location)
    t0 = time.monotonic()
    reports = run.reports(item)
    for report in reports:
        report.duration = 0.0
    reports[-1].duration = time.monotonic() - t0
    for report in reports:
        item.ihook.pytest_runtest_logreport(report=report)
    item.ihook.pytest_runtest_logfinish(nodeid=item.nodeid,
                                        location=item.location)
    return True


def pytest_runtest_logreport(report):
    """In an interpreter that runs a file for another session: every
    report goes where that session reads it."""
    from tests import ahead
    if ahead.REPORTS_TO in os.environ:
        ahead.write_report(_config, report)


# -- the process ends when pytest does --------------------------------------

_PYTESTS_OWN_PROCESS = os.path.basename(sys.argv[0]) in ("pytest", "py.test") \
    or sys.argv[0].endswith(os.path.join("pytest", "__main__.py"))
_exit_status = None


def pytest_sessionfinish(session, exitstatus):
    global _exit_status
    _exit_status = int(exitstatus)


@pytest.hookimpl(trylast=True)
def pytest_unconfigure(config):
    """The last hook of a session: every fixture is torn down and the
    summary is written. What is left to a process that pytest started
    is the interpreter's shutdown, which no line of the summary counts
    and nobody reads: after the driver's run of PR 43's tree it was
    still going 32 s after pytest's last line, when `timeout` cut a run
    whose every test had passed. It is the shutdown as a whole, not one
    library's handler: after 90 tests (800,000 objects alive) the
    process ends 3.8 s after this hook, 2.2 s of them before the first
    ``atexit`` handler runs and 1.8 s in jax's ``clean_up``
    (``clear_backends`` drops every traced and compiled program); with
    that handler unregistered the rest takes 5.2 s. So the process
    leaves here, with the exit status pytest chose and both streams
    flushed. A program that calls ``pytest.main()`` itself gets its
    answer back as ever."""
    if _exit_status is None or not _PYTESTS_OWN_PROCESS:
        return
    reporter = config.pluginmanager.get_plugin("terminalreporter")
    for stream in (getattr(reporter, "_tw", None), sys.stdout, sys.stderr,
                   sys.__stdout__, sys.__stderr__):
        if stream is not None:
            stream.flush()
    os._exit(_exit_status)


# -- where the time went ----------------------------------------------------

def pytest_terminal_summary(terminalreporter):
    by_file, by_test = defaultdict(float), defaultdict(float)
    for reports in terminalreporter.stats.values():
        for rep in reports:
            if getattr(rep, "when", None) in ("setup", "call", "teardown"):
                by_file[rep.nodeid.split("::", 1)[0]] += rep.duration
                by_test[rep.nodeid] += rep.duration

    def largest_first(seconds):
        return sorted(seconds.items(), key=lambda kv: -kv[1])

    wall, tests = time.monotonic() - _RUN_STARTED, sum(by_file.values())
    terminalreporter.section("the suite's clock")
    terminalreporter.write_line(
        f"{wall:.1f} s of wall, {tests:.1f} s in tests: "
        f"{tests / SUITE_LIMIT_S:.1%} of the {SUITE_LIMIT_S:g} s limit")
    terminalreporter.section("seconds by file (set-up + call + teardown)")
    for name, s in largest_first(by_file):
        terminalreporter.write_line(f"{s:9.2f} s  {name}")
    if _file_runs:
        terminalreporter.section(
            "seconds by file, in an interpreter of its own beside these")
        for name, s in largest_first({path: run.seconds or 0.0 for path, run
                                      in _file_runs.items()}):
            terminalreporter.write_line(f"{s:9.2f} s  {name}")
    terminalreporter.section("the twenty longest tests")
    for name, s in largest_first(by_test)[:20]:
        terminalreporter.write_line(f"{s:9.2f} s  {name}")
