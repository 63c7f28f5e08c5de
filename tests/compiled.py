"""One compilation where a test would dispatch op by op.

Outside ``jit`` every primitive of a reference's forward and backward
is a program of its own to trace, lower and compile: some forty a
kernel case, and most of such a test's seconds."""

import jax


def out_and_vjp(f, cotangent, *args):
    """``f(*args)`` and its vjp at ``cotangent``, as one program."""

    @jax.jit
    def run(cotangent, *args):
        out, vjp = jax.vjp(f, *args)
        return out, vjp(cotangent)

    return run(cotangent, *args)


@jax.jit
def momentum_step(w, m, g):
    """A step of ``optax.sgd(0.01, momentum=0.9)`` by hand, as one
    program: the parameters and the momentum after it (the momentum
    starts at zeros, so the first is the gradient itself)."""
    m = jax.tree_util.tree_map(lambda m_, g_: g_ + 0.9 * m_, m, g)
    return jax.tree_util.tree_map(lambda w_, m_: w_ - 0.01 * m_, w, m), m


def weights_under(shapes, fan_ins, seed, stream, under):
    """The part of ``weights.make_tree(shapes, fan_ins, seed, stream)``
    below the path ``under`` ("params/layer_0/moe"), to the bit, and
    nothing else of it: each leaf is drawn from the key its place in
    the WHOLE tree's sorted paths gives it, and the program that draws
    them has these leaves alone (the whole of a four-layer model's tree
    is 4 to 8 s of compiling for a test that reads one layer's)."""
    from .chip_bench import _paths  # noqa: F401  (makes chipbench importable)
    from chipbench import weights
    mine = {path[len(under) + 1:]: (i, path, shape) for i, (path, shape)
            in enumerate(weights.flat_shapes(shapes).items())
            if path.startswith(under + "/")}

    @jax.jit
    def build(key):
        return weights.nest({
            name: weights.leaf_value(jax.random.fold_in(key, i), path,
                                     shape, fan_ins.get(path, 1))
            for name, (i, path, shape) in mine.items()})

    return build(weights.seed_key(seed, stream))
