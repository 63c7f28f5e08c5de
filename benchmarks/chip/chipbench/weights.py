"""Seeded weights and batches, made on the device in one jitted call.

The benchmark makes the state a run starts from, as a user's job would
restore a checkpoint: the program gets the arrays, the plain reference
makes the same arrays again from the same seed with this same code,
and neither takes anything from the other.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0):
    """A key for any whole-number seed (the driver's pass 2**31)."""
    key = jax.random.key(int(seed) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, int(seed) >> 31)
    return jax.random.fold_in(key, stream)


def leaf_value(key, path: str, shape, fan_in: int):
    """One leaf by its name: ``scale`` and ``var`` are ones, ``bias``
    and ``mean`` zeros, kernels and embeddings normal with a standard
    deviation of ``fan_in ** -0.5``."""
    name = path.rsplit("/", 1)[-1]
    if name in ("scale", "var"):
        return jnp.ones(shape, jnp.float32)
    if name in ("bias", "mean"):
        return jnp.zeros(shape, jnp.float32)
    return jax.random.normal(key, shape, jnp.float32) \
        * jnp.float32(1.0 / math.sqrt(fan_in))


def flat_shapes(tree, prefix=""):
    """``{"a/b/c": shape}`` from a nested dict of shapes, sorted."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.update(flat_shapes(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = tuple(v)
    return out


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def make_tree(shapes: dict, fan_ins: dict, seed: int, stream: int,
              sharding=None):
    """The nested tree of f32 arrays for ``shapes`` (a nested dict of
    shapes), leaf ``i`` in sorted path order drawn from key ``i``,
    placed by ``sharding`` where one is given."""
    flat = flat_shapes(shapes)

    def build(key):
        return nest({
            path: leaf_value(jax.random.fold_in(key, i), path, shape,
                             fan_ins.get(path, 1))
            for i, (path, shape) in enumerate(flat.items())})

    return jax.jit(build, out_shardings=sharding)(seed_key(seed, stream))
