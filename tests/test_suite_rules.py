"""The suite's own rules (``tests/conftest.py``): CPU compiles skip the
optimiser, one bytecode cache serves every interpreter of a run, every
test has a time limit, the pytest process keeps SIGTERM's disposition,
and a run prints how much of its clock it used and where the time
went."""

import os
import re
import signal
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# What the driver counts passes with.
_DOTS = re.compile(r"^[.FEsx]+( *\[ *[0-9]+%\])?$")


def test_cpu_compiles_skip_the_optimiser():
    """On here and in everything spawned from here; off in
    ``test_chip_compile.py``, which says so itself."""
    import jax
    assert jax.config.read("jax_disable_most_optimizations")
    assert os.environ["JAX_DISABLE_MOST_OPTIMIZATIONS"] == "1"


def test_a_spawned_interpreter_shares_the_runs_bytecode_cache():
    """Here and in everything spawned from here: a rank reads the
    bytecode an earlier process wrote, and writes what is missing."""
    assert sys.pycache_prefix and not sys.dont_write_bytecode
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; print(sys.pycache_prefix, sys.dont_write_bytecode)"],
        capture_output=True, text=True, timeout=60, check=True)
    assert out.stdout.split() == [sys.pycache_prefix, "False"]


def _run_under_the_conftest(path, body):
    """``body`` as a test file of its own, run under this suite's
    conftest in a process of its own; the result and its seconds."""
    path.write_text(textwrap.dedent(body))
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "tests.conftest", str(path),
         "-q", "-p", "no:cacheprovider", "-p", "no:xdist", "-p",
         "no:randomly"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    return out, time.monotonic() - t0


@pytest.fixture(scope="module")
def inner_run(tmp_path_factory):
    """A run of three tests: one sleeps past a one-second limit."""
    return _run_under_the_conftest(
        tmp_path_factory.mktemp("inner") / "test_inner.py", """
        import time
        import pytest

        def test_before():
            pass

        @pytest.mark.time_limit(1)
        def test_oversleeps():
            time.sleep(60)

        def test_after():
            pass
    """)


def test_a_test_past_its_limit_fails_and_the_run_goes_on(inner_run):
    out, seconds = inner_run
    assert out.returncode == 1, out.stdout + out.stderr
    assert seconds < 45, seconds
    assert "2 passed" in out.stdout and "1 failed" in out.stdout
    assert "test_oversleeps exceeded its time limit of 1 s" in out.stdout
    # every thread's stack: the sleeping frame is in the report
    assert "in test_oversleeps" in out.stdout


def test_the_run_prints_where_its_time_went(inner_run):
    out, _ = inner_run
    lines = out.stdout.splitlines()
    by_file = lines.index(next(
        line for line in lines if "seconds by file" in line))
    longest = lines.index(next(
        line for line in lines if "the twenty longest tests" in line))
    assert "test_inner.py" in lines[by_file + 1]
    assert "test_inner.py::test_oversleeps" in lines[longest + 1]
    # the driver's count of passes still reads the progress lines only
    dots = "".join(line for line in lines if _DOTS.match(line))
    assert dots.count(".") == 2 and dots.count("F") == 1, dots


def test_the_run_says_how_much_of_its_clock_it_used(inner_run):
    """One line before the tables: the wall, the tests' own seconds and
    their share of the limit the driver's command runs under."""
    from tests.conftest import SUITE_LIMIT_S
    out, seconds = inner_run
    lines = out.stdout.splitlines()
    clock = lines.index(next(
        line for line in lines if "the suite's clock" in line))
    assert clock < lines.index(next(
        line for line in lines if "seconds by file" in line))
    said = re.fullmatch(
        r"(\d+\.\d) s of wall, (\d+\.\d) s in tests: "
        r"(\d+\.\d)% of the 1470 s limit", lines[clock + 1])
    assert said and SUITE_LIMIT_S == 1470, lines[clock + 1]
    wall, tests, share = map(float, said.groups())
    # the test that oversleeps is cut at its second
    assert 1.0 <= tests <= wall <= seconds, (tests, wall, seconds)
    assert abs(share - 100 * tests / SUITE_LIMIT_S) < 0.1, (share, tests)


def test_a_world_leaves_sigterm_as_it_found_it():
    import horovod_tpu as hvd
    from horovod_tpu.common import selfop
    before = signal.getsignal(signal.SIGTERM)
    assert before is not selfop._on_sigterm
    hvd.init()
    during = signal.getsignal(signal.SIGTERM)
    hvd.shutdown()
    assert during is before and signal.getsignal(signal.SIGTERM) is before


def test_a_sigterm_ends_the_run_at_once_inside_a_world_too(tmp_path):
    """The clock runs out while a test holds a world: the run ends there
    and then, not a grace later by SIGKILL."""
    out, seconds = _run_under_the_conftest(tmp_path / "test_inner.py", """
        import os
        import signal
        import time

        def test_holds_a_world():
            import horovod_tpu as hvd
            hvd.init()
            os.kill(os.getpid(), signal.SIGTERM)
            time.sleep(60)
    """)
    assert out.returncode == -signal.SIGTERM, out.stdout + out.stderr
    assert seconds < 30, seconds
