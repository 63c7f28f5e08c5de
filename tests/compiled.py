"""One compilation where a test would dispatch op by op, and the
compilations a file needs beside one another.

Outside ``jit`` every primitive of a reference's forward and backward
is a program of its own to trace, lower and compile: some forty a
kernel case, and most of such a test's seconds.

Tracing and lowering are Python and hold the interpreter; what XLA does
with a lowered program (``Lowered.compile()``) releases it and takes a
core. So a file that needs whole programs (a model's loss and
gradients, its reference's, its step) lowers them in one module fixture
at the file's start and hands them to ``beside``: the session's one
pool of threads compiles them beside one another and beside the tests
that run before the first that asks for one, where ``jit`` would
compile each at its first call, one after the other, on one of the
run's eight cores."""

import concurrent.futures
import os

import jax

_pool = None


def pool() -> concurrent.futures.ThreadPoolExecutor:
    """The session's one pool of compiling threads: half the cores the
    run may use, two at least and four at most (the TPU's compiler takes
    a core or two a program, the CPU's one)."""
    global _pool
    if _pool is None:
        _pool = concurrent.futures.ThreadPoolExecutor(
            max(2, min(4, len(os.sched_getaffinity(0)) // 2)),
            thread_name_prefix="compiles")
    return _pool


def start(lowered) -> concurrent.futures.Future:
    """``lowered.compile()`` on the pool: the executable, or what the
    compiler refused it with, when its ``result()`` is asked for."""
    return pool().submit(lowered.compile)


class beside:
    """``beside(name=jax.jit(f).lower(*arguments), ...)``: programs
    lowered on the caller's thread go to the pool at once;
    ``programs[name]`` is the executable, waited for when it is asked
    for (or raises what the compiler refused the program with). What
    has no ``compile()`` (the arguments a program was lowered for) is
    kept under its name as it came."""

    def __init__(self, **lowered):
        self._made = {
            name: start(value) if hasattr(value, "compile") else value
            for name, value in lowered.items()}

    def __getitem__(self, name):
        made = self._made[name]
        return made.result() if isinstance(
            made, concurrent.futures.Future) else made

    def cancel(self):
        """What the pool has not begun it need not begin."""
        for made in self._made.values():
            if isinstance(made, concurrent.futures.Future):
                made.cancel()


class ahead_of:
    """The programs of a parametrised test's cases, a few cases ahead
    of the one that asks: ``programs = ahead_of(lower, cases)`` in a
    module fixture, where ``lower(case)`` gives the case's lowered
    programs by name; ``programs(case)[name]`` is an executable. The
    first to ask for a case lowers it and the ``ahead`` cases after it
    in ``cases``' order (the tests' order), so the pool compiles a case
    while the tests before it lower, run and assert: a test then takes
    what it lowers, and what XLA takes beside it nobody waits for. A
    case that is not in ``cases`` is lowered when it is asked for."""

    def __init__(self, lower, cases, ahead=2):
        self._lower, self._cases, self._ahead = lower, list(cases), ahead
        self._programs = {}

    def _start(self, case):
        try:
            return beside(**self._lower(case))
        except Exception as refused:    # the asking test's to report
            return refused

    def __call__(self, case):
        at = self._cases.index(case) if case in self._cases \
            else len(self._cases)
        for soon in (case, *self._cases[at + 1:at + 1 + self._ahead]):
            if soon not in self._programs:
                self._programs[soon] = self._start(soon)
        if isinstance(self._programs[case], Exception):
            raise self._programs[case]
        return self._programs[case]

    def cancel(self):
        for programs in self._programs.values():
            if isinstance(programs, beside):
                programs.cancel()


def step_on_a_mesh_of_one(train_step, model, params, tokens):
    """``train_step(model, tx, mesh)`` (one of ``models/train_steps.py``)
    lowered over a mesh of one device, under the distributed optimizer
    over SGD at 0.01 with momentum 0.9; and the state it was lowered
    for, on the mesh, where the step leaves its state (one program for
    every step, not one for the first and one for the rest): a copy of
    ``params``, the optimizer's state and ``tokens``, the first two
    donated to the step's first call."""
    import jax.numpy as jnp
    import optax

    import horovod_tpu.jax as hvd
    from horovod_tpu import spmd
    mesh = spmd.create_mesh({"data": 1}, devices=jax.devices()[:1])
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                  axis="data")
    rep = spmd.replicated_sharding(mesh)
    p = jax.device_put(jax.tree_util.tree_map(jnp.array, params), rep)
    state = (p, jax.device_put(tx.init(p), rep),
             jax.device_put(tokens, spmd.batch_sharding(mesh)))
    return train_step(model, tx, mesh).lower(*state), state


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
