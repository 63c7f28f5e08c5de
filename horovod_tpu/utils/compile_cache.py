"""Where JAX's persistent compilation cache lives.

One rule for every entry point (chip_smoke.py, bench.py, the synthetic
benchmark example, tests/conftest.py): where the operator set
``JAX_COMPILATION_CACHE_DIR`` that directory is used and nothing is
set in code; otherwise the cache is ``<checkout>/.jax_cache``. The
path is part of the cache key, so it is fixed — never made from
``tempfile``, a pid or the clock, which would never hit twice.
"""

from __future__ import annotations

import os
import sys

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The directory in use: the environment's, else the checkout's."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point this process and every child it spawns at the cache and
    return the directory. A no-op where the environment already names
    one (jax reads the variable itself)."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        os.environ[_ENV] = path  # spawned ranks inherit it
        if "jax" in sys.modules:
            # jax read the variable at import; tell the live config too.
            sys.modules["jax"].config.update(
                "jax_compilation_cache_dir", path)
    return path
