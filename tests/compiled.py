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
