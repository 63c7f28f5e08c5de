"""The suite's own rules (``tests/conftest.py``): CPU compiles skip the
optimiser, one bytecode cache serves every interpreter of a run, every
test has a time limit, the pytest process keeps SIGTERM's disposition,
a run prints how much of its clock it used and where the time went,
and the process ends when pytest does, with the exit code pytest
chose."""

import os
import re
import signal
import subprocess
import sys
import textwrap
import time
import types

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


def _run_under_the_conftest(path, body, command=("-m", "pytest")):
    """``body`` as a test file of its own, run under this suite's
    conftest in a process of its own, read as the driver reads it (both
    streams through one pipe); the result (``returncode``, ``stdout``,
    ``after_its_last_line``: the seconds from the last line it wrote to
    its end) and the seconds of the whole."""
    path.write_text(textwrap.dedent(body))
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, *command, "-p", "tests.conftest", str(path),
         "-q", "-p", "no:cacheprovider", "-p", "no:xdist", "-p",
         "no:randomly"],
        cwd=REPO, text=True, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)
    lines, last_line_at = [], t0
    try:
        for line in proc.stdout:
            lines.append(line)
            last_line_at = time.monotonic()
        returncode = proc.wait(timeout=120)
    finally:
        proc.kill()     # a test's limit may have ended the reading
    ended = time.monotonic()
    return types.SimpleNamespace(
        returncode=returncode, stdout="".join(lines),
        after_its_last_line=ended - last_line_at), ended - t0


# What an inner run plants for the interpreter's shutdown to do: a
# library's handler that would take five seconds and say so.
_A_SLOW_SHUTDOWN = """
        import atexit

        @atexit.register
        def a_librarys_handler():
            import time
            time.sleep(5)
            print("the interpreter shut down")
"""


@pytest.fixture(scope="module")
def inner_run(tmp_path_factory):
    """A run of three tests: one sleeps past a one-second limit."""
    return _run_under_the_conftest(
        tmp_path_factory.mktemp("inner") / "test_inner.py", _A_SLOW_SHUTDOWN
        + """
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


@pytest.fixture(scope="module")
def passing_run(tmp_path_factory):
    """A run of two tests that pass."""
    return _run_under_the_conftest(
        tmp_path_factory.mktemp("passing") / "test_inner.py",
        _A_SLOW_SHUTDOWN + """
        def test_one():
            pass

        def test_another():
            print("said by a test that passed")
    """)


def test_a_test_past_its_limit_fails_and_the_run_goes_on(inner_run):
    out, seconds = inner_run
    assert out.returncode == 1, out.stdout
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


@pytest.mark.parametrize("run,code,verdict,dots", [
    ("inner_run", 1, r"1 failed, 2 passed in \d+\.\d+s", ".F."),
    ("passing_run", 0, r"2 passed in \d+\.\d+s", ".."),
], ids=["a_run_that_fails", "a_run_that_passes"])
def test_the_process_ends_when_pytest_does(request, run, code, verdict, dots):
    """What the driver reads of a run is there whichever way it ends:
    pytest's exit code, every line of the summary through the pipe with
    pytest's verdict the last of them, the dots its ``grep`` counts. And
    the process ends with that line: the interpreter's shutdown (here a
    handler of five seconds; in a whole run of the suite the taking
    apart of what a quarter of an hour left in memory) is nothing the
    clock line counts and nothing anybody reads."""
    out, _ = request.getfixturevalue(run)
    assert out.returncode == code, out.stdout
    lines = out.stdout.splitlines()
    assert re.fullmatch(verdict, lines[-1].strip("= ")), lines[-1]
    assert "".join(line.split()[0] for line in lines
                   if _DOTS.match(line)) == dots
    assert any("the suite's clock" in line for line in lines)
    assert "the interpreter shut down" not in out.stdout
    assert out.after_its_last_line < 2.0, out.after_its_last_line


def test_a_program_that_calls_pytest_gets_its_answer_back(tmp_path):
    """``pytest.main()`` called by a program of somebody else's (an
    editor's runner) returns: only a process that pytest itself started
    is ended from the session's last hook."""
    out, _ = _run_under_the_conftest(
        tmp_path / "test_inner.py", """
        def test_one():
            pass
    """, command=("-c", "import sys, pytest; "
                  "print('back with', int(pytest.main(sys.argv[1:])))"))
    assert out.returncode == 0, out.stdout
    assert out.stdout.splitlines()[-1] == "back with 0", out.stdout


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
    assert out.returncode == -signal.SIGTERM, out.stdout
    assert seconds < 30, seconds
