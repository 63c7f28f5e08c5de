"""Test configuration.

Force JAX onto a virtual 8-device CPU platform *before* jax is imported
anywhere, so SPMD/mesh tests exercise real multi-device sharding without
TPU hardware (the driver separately dry-runs the multi-chip path; see
__graft_entry__.py)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import tempfile  # noqa: E402

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
# (a temporary name would never hit twice); compiles under jax's
# default 1 s floor are not cached.
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
