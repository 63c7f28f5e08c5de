"""The suite's own rules (``tests/conftest.py``): CPU compiles skip the
optimiser, one bytecode cache serves every interpreter of a run, every
test has a time limit, the pytest process keeps SIGTERM's disposition,
a run prints how much of its clock it used and where the time went,
the process ends when pytest does, with the exit code pytest chose,
whole programs compile beside one another on the session's one pool
(``tests/compiled.py``), and an example's run is started ahead or made
by the test that asks (``tests/ahead.py``)."""

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


def _run_under_the_conftest(path, body, command=("-m", "pytest"),
                            beside=None):
    """``body`` as a test file of its own (and ``beside``: bodies by
    name, further files beside it, in that order after it), run under
    this suite's conftest in a process of its own, read as the driver
    reads it (both streams through one pipe); the result
    (``returncode``, ``stdout``, ``after_its_last_line``: the seconds
    from the last line it wrote to its end) and the seconds of the
    whole."""
    path.write_text(textwrap.dedent(body))
    others = [path.with_name(name) for name in beside or ()]
    for other in others:
        other.write_text(textwrap.dedent(beside[other.name]))
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, *command, "-p", "tests.conftest", str(path),
         *map(str, others),
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


def test_the_pool_compiles_two_programs_beside_one_another():
    """``compiled.beside``: each program is lowered where it is asked
    for (the main thread: tracing is Python), both are in the hands of
    the session's one pool at once (each ``compile`` waits for the
    other to have begun), and both executables come back."""
    import threading

    import jax
    import jax.numpy as jnp

    from tests import compiled

    lowered_on, compiled_on = [], []
    both_began = threading.Barrier(2, timeout=60)

    class Noting:
        def __init__(self, f, *args):
            lowered_on.append(threading.current_thread())
            self.lowered = jax.jit(f).lower(*args)

        def compile(self):
            compiled_on.append(threading.current_thread())
            both_began.wait()
            return self.lowered.compile()

    x = jnp.arange(4.0)
    programs = compiled.beside(twice=Noting(lambda x: 2 * x, x),
                               squared=Noting(lambda x: x * x, x))
    assert programs["twice"](x).tolist() == [0, 2, 4, 6]
    assert programs["squared"](x).tolist() == [0, 1, 4, 9]
    assert lowered_on == [threading.main_thread()] * 2
    pool = compiled.pool()
    assert pool is compiled.pool() and 2 <= pool._max_workers <= 4
    assert len(set(compiled_on)) == 2 \
        and set(compiled_on) <= set(pool._threads)
    assert compiled.start(Noting(lambda x: -x, x).lowered).result()(x) \
        .tolist() == [-0.0, -1, -2, -3]
    assert len(pool._threads) <= pool._max_workers


def test_an_example_nobody_started_is_run_by_its_own_test(monkeypatch):
    """``pytest tests/test_examples.py`` starts its examples ahead; a
    test that finds its example not started (picked by hand, or from a
    program that imports the file) runs it there and then, and one that
    was started is not run again."""
    from tests import ahead, test_examples
    ran = []

    def run_example(name):
        ran.append(name)
        return ["loss 2.0 -> 1.0, said " + name], None

    monkeypatch.setattr(test_examples, "run_example", run_example)
    monkeypatch.setattr(ahead, "_started", {})  # a session that began none
    test_examples.test_mxnet_mnist()
    assert ran == ["mxnet_mnist"]
    assert test_examples.printed("jax_mnist").endswith("said jax_mnist")
    assert ran == ["mxnet_mnist", "jax_mnist"]
    # the session's start: the selected tests' examples, in the table's
    # order, each once
    test_examples.start_ahead([
        types.SimpleNamespace(originalname=name) for name in (
            "test_zero_fsdp", "test_jax_mnist",
            "test_every_example_is_covered")])
    assert list(ahead._started) == [("example", "jax_mnist"),
                                    ("example", "zero_fsdp")]
    test_examples.test_jax_mnist()
    test_examples.test_jax_mnist()
    assert test_examples.printed("zero_fsdp").endswith("said zero_fsdp")
    assert sorted(ran[2:]) == ["jax_mnist", "zero_fsdp"]


def test_a_file_runs_in_an_interpreter_of_its_own_beside_the_others(
        tmp_path):
    """A file marked ``interpreter_of_its_own`` in a session with
    another file: its tests run in another process, each is reported in
    its turn as that process reported it (a failure with what it said),
    and a test the process ended without reporting fails with what the
    process said; the file beside it runs in the session's process,
    after what was started ahead. Alone the file runs in the session's
    process."""
    own = """
        import os
        import pytest

        pytestmark = pytest.mark.interpreter_of_its_own

        def test_passes():
            with open(os.path.join(os.path.dirname(__file__), "ran"),
                      "a") as f:
                f.write(f"own {os.getpid()}\\n")

        def test_fails():
            assert 1 + 1 == 3, "said by the test that failed"

        @pytest.mark.parametrize("n", [1, 2])
        def test_cases(n):
            assert n

        def test_ends_the_interpreter():
            os._exit(7)

        def test_never_runs():
            pass
    """
    here = """
        import os

        def test_here():
            with open(os.path.join(os.path.dirname(__file__), "ran"),
                      "a") as f:
                f.write(f"here {os.getpid()}\\n")
    """
    out, _ = _run_under_the_conftest(tmp_path / "test_own.py", own,
                                     beside={"test_here.py": here})
    lines = out.stdout.splitlines()
    assert out.returncode == 1, out.stdout
    assert "".join(line.split()[0] for line in lines
                   if _DOTS.match(line)) == ".F..FF.", out.stdout
    assert "said by the test that failed" in out.stdout
    assert "ended (7) without this test's report" in out.stdout
    assert "it said:\n  | .F.." in out.stdout
    assert "3 failed, 4 passed" in lines[-1], lines[-1]
    assert any("in an interpreter of its own" in line for line in lines)
    (own_said, own_pid), (here_said, here_pid) = (
        line.split() for line in (tmp_path / "ran").read_text().splitlines())
    assert (own_said, here_said) == ("own", "here") and own_pid != here_pid
    # alone, the file runs where it is asked for
    (tmp_path / "ran").unlink()
    alone, _ = _run_under_the_conftest(tmp_path / "test_own.py", own)
    assert "ended (7)" not in alone.stdout and alone.returncode == 7
