"""jaxshim — the ONE sanctioned JAX version-compat boundary.

Every mesh/sharding construction in this tree routes through here, and
the ``jax_compat`` hvdlint analyzer (tools/hvdlint/jax_compat.py)
enforces it: JAX moves its partitioning surface roughly once a year
(``jax.experimental.maps`` / ``sharded_jit`` → ``pjit`` →
``jax.sharding`` + ``jax.experimental.shard_map`` → top-level
``jax.shard_map``), and every move has historically rotted exactly the
modules that call the APIs directly — the 52-test shard_map family was
red from PR 3 to PR 20 for this reason alone. One module pays the
version tax; everyone else imports semantics.

Policy:

* a wrapper that must differ between supported releases is gated on
  :func:`jax_version` (parsed per call so tests can mock a release).
  None is today: the tree runs on one installation (jax 0.9), so
  ``shard_map`` and ``axis_size`` call the current spelling directly;
* the supported floor is pinned in :data:`SUPPORTED_JAX_FLOOR` (also
  pinned in pyproject + README); the analyzer's API table flags any
  symbol that does not exist across the whole supported span;
* new JAX surface is adopted by *extending this module* — never by
  calling the new API at a use site.
"""

from __future__ import annotations

import math
import re
from typing import Dict, Optional, Sequence

# The oldest JAX this tree supports (pinned in pyproject.toml and
# README; tools/hvdlint/jax_compat.py imports it for its API table).
# shard_map/axis_size below need jax >= 0.5; raising this literal, the
# analyzer's twin and the pins together is ROADMAP D9.
SUPPORTED_JAX_FLOOR = (0, 4, 37)


def _parse_version(v: str) -> tuple:
    """'0.4.37' / '0.7.0.dev20260101+abc' -> (0, 4, 37) / (0, 7, 0)."""
    parts = []
    for piece in v.split(".")[:3]:
        m = re.match(r"\d+", piece)
        if not m:
            break
        parts.append(int(m.group()))
    return tuple(parts) if parts else (0,)


def jax_version() -> tuple:
    """The running jax release as an int tuple. Read per call (not
    cached at import) so the version gate is unit-testable against a
    mocked ``jax.__version__``."""
    import jax
    return _parse_version(jax.__version__)


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------

def make_mesh(axes: Optional[Dict[str, int]] = None, devices=None,
              allow_split_physical_axes: bool = False):
    """Build a ``jax.sharding.Mesh`` from ``{axis_name: size}``.

    At most one size may be ``-1`` (filled with the remaining
    devices); default is one ``'data'`` axis over every visible
    device. On multi-host platforms the device order comes from
    ``mesh_utils.create_device_mesh`` so trailing axes map to ICI
    neighbours and leading axes to DCN.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if not axes:
        axes = {"data": n}
    names = tuple(axes.keys())
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may have size -1")
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        if known == 0 or n % known:
            raise ValueError(
                f"cannot infer -1 axis: {n} devices not divisible "
                f"by {known}")
        sizes[sizes.index(-1)] = n // known
    if math.prod(sizes) != n:
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {math.prod(sizes)} "
            f"devices but {n} are visible")
    dev_array = _device_array(tuple(sizes), devices,
                              allow_split_physical_axes)
    return Mesh(dev_array, names)


def _device_array(sizes: tuple, devices, allow_split: bool):
    """Topology-aware device grid; plain reshape when mesh_utils cannot
    place this platform (CPU test meshes, forced host platforms)."""
    import numpy as np
    from jax.experimental import mesh_utils
    try:
        return mesh_utils.create_device_mesh(
            sizes, devices=devices,
            allow_split_physical_axes=allow_split)
    except Exception:
        return np.asarray(devices).reshape(sizes)


def make_hybrid_mesh(ici_axes: Dict[str, int], dcn_axes: Dict[str, int]):
    """Two-level mesh for multi-slice jobs: ``dcn_axes`` shard across
    slices, ``ici_axes`` within a slice."""
    from jax.sharding import Mesh
    from jax.experimental import mesh_utils

    names = tuple(dcn_axes.keys()) + tuple(ici_axes.keys())
    dev_array = mesh_utils.create_hybrid_device_mesh(
        tuple(ici_axes.values()),
        dcn_mesh_shape=tuple(dcn_axes.values()))
    return Mesh(dev_array, names)


def make_raw_mesh(dev_array, axis_names: Sequence[str]):
    """``jax.sharding.Mesh`` from an explicit device grid — for callers
    that computed their own placement (the XLA backend's proc meshes)."""
    from jax.sharding import Mesh
    return Mesh(dev_array, tuple(axis_names))


# ---------------------------------------------------------------------------
# sharding construction
# ---------------------------------------------------------------------------

def partition_spec(*axis_names):
    """``jax.sharding.PartitionSpec(*axis_names)``. Stable since jax
    0.4.6 (before that it lived in jax.experimental.pjit — below the
    supported floor, kept here so the table has one citation site)."""
    from jax.sharding import PartitionSpec
    return PartitionSpec(*axis_names)


def named_sharding(mesh, spec):
    """``NamedSharding(mesh, spec)``; ``spec`` is a PartitionSpec (or
    anything PartitionSpec accepts when given as a tuple)."""
    from jax.sharding import NamedSharding, PartitionSpec
    if not isinstance(spec, PartitionSpec):
        spec = PartitionSpec(*spec) if isinstance(spec, (tuple, list)) \
            else PartitionSpec(spec)
    return NamedSharding(mesh, spec)


def with_sharding_constraint(x, mesh, spec):
    """Anchor an intermediate's sharding inside jit. Modern jax takes a
    Sharding directly; the pre-0.4 pjit spelling is below the floor."""
    import jax
    return jax.lax.with_sharding_constraint(x, named_sharding(mesh, spec))


# ---------------------------------------------------------------------------
# shard_map + collectives
# ---------------------------------------------------------------------------

def shard_map(body, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map``. ``check=False`` (the project default)
    disables the static replication checker (``check_vma``) —
    collectives guarantee their own output sharding, which the
    checker cannot see."""
    import jax
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def axis_size(axis) -> int:
    """Static size of a named mesh axis, inside shard_map/pmap."""
    import jax
    return jax.lax.axis_size(axis)


def psum_scatter(x, axis, scatter_dimension: int = 0, tiled: bool = True):
    """``jax.lax.psum_scatter`` — stable across the supported span;
    wrapped so the reduce-scatter spelling has one version-gateable
    call site (its kwargs are the next most likely to move)."""
    import jax
    return jax.lax.psum_scatter(x, axis,
                                scatter_dimension=scatter_dimension,
                                tiled=tiled)


__all__ = [
    "SUPPORTED_JAX_FLOOR", "jax_version",
    "make_mesh", "make_hybrid_mesh", "make_raw_mesh",
    "partition_spec", "named_sharding", "with_sharding_constraint",
    "shard_map", "axis_size", "psum_scatter",
]
