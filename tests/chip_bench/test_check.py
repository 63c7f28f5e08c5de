"""How ``correct`` is decided, at a size a test can hold: the program
agrees with the plain reference inside the rehearsal's limits, the
same program with bfloat16 parameters does not, and a run whose timed
path is broken underneath comes out not correct."""

import argparse
import time

import pytest

from . import _paths
from chipbench import check, harness

# with the eager cell that is kept for a later PR (PERF.md, Open questions)
M = _paths.manifest_with_kept()


def spec_of(cell):
    return harness.resolve_cell(M, cell, rehearse=True)


@pytest.fixture()
def world():
    import horovod_tpu.jax as hvd
    hvd.init()
    yield hvd
    hvd.shutdown()


def gaps(spec, seed, param_dtype=None, control_leaves=""):
    program = harness.Program(spec, seed, 1, param_dtype=param_dtype,
                              control_leaves=control_leaves)
    assert program.chips == spec["cell"]["chips"]
    state = program.make_state()
    batch = program.make_batch(0, program.batch_sharding)
    program.compile(state, batch)
    got = program.first_steps(state, batch)
    return check.compare(got, program.reference(),
                         spec["config"]["check"]["limits"])


@pytest.mark.parametrize("cell, leaves", [
    ("lm-injit-1chip", ("", "lm_head")),    # all parameters; the head alone
    ("lm-injit-4chip", ("lm_head",)),   # four devices, rows in four groups
    ("resnet50-eager-1rank", ("",)), ("resnet50-injit-1chip", ("",))])
def test_program_passes_and_bf16_parameters_fail(world, cell, leaves):
    import jax.numpy as jnp
    spec = spec_of(cell)
    seed = 2**31 + 7
    sound = gaps(spec, seed)
    assert all(c["ok"] for c in sound.values()), sound
    for only in leaves:
        control = gaps(spec, seed, param_dtype=jnp.bfloat16,
                       control_leaves=only)
        failed = [k for k, c in control.items() if not c["ok"]]
        assert "update_norm_gap" in failed, (only, control)


def test_worst_leaf_gap_is_held_against_the_median_leaf():
    # the third leaf is all but zero: its gap is measured against the
    # median leaf's norm (1.0), not its own
    assert check.worst_leaf_gap([1.0, 2.0, 1e-9], [1.0, 2.0, 3e-9]) \
        == pytest.approx(2e-9)
    assert check.worst_leaf_gap([1.1, 2.0, 0.0], [1.0, 2.0, 0.0]) \
        == pytest.approx(0.1)
    with pytest.raises(ValueError):
        check.worst_leaf_gap([1.0], [1.0, 2.0])


def run_in_process(cell, monkeypatch, **patches):
    for name, value in patches.items():
        monkeypatch.setattr(harness.Program, name, value)
    args = argparse.Namespace(
        workload=cell, seed=3, seconds=0.5, trace=0, rehearse=True,
        t0=time.time(), launched=None)
    return harness.run_rank(args, M)


def test_a_sound_run_in_process_is_correct(monkeypatch):
    assert run_in_process("resnet50-eager-1rank", monkeypatch)["correct"]


@pytest.mark.parametrize("cell", ["lm-injit-1chip", "resnet50-eager-1rank"])
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        cell, monkeypatch):
    """The rest of a run as it is, the timed path broken underneath:
    the step computes its loss and hands back the state it was given."""
    real = harness.Program.step

    def stuck(self, state, batch, stop=0.0):
        import jax
        kept = jax.tree_util.tree_map(lambda x: x + 0, state)
        _, loss, stop = real(self, state, batch, stop)
        return kept, loss, stop

    result = run_in_process(cell, monkeypatch, step=stuck)
    assert result["correct"] is False
    assert not result["checks"]["update_norm_gap"]["ok"]
    assert not result["checks"]["window_loss_falls"]["ok"]


def test_an_exchange_left_out_is_not_correct(monkeypatch):
    """An eager step that drops its gradients' exchange and applies
    gradients a fifth the size: the first gradient's norm gives it
    away."""
    import horovod_tpu.jax as hvd

    def shrunk(tree, **kw):
        import jax
        return jax.tree_util.tree_map(lambda g: g * 0.2, tree)

    monkeypatch.setattr(hvd, "allreduce_gradients", shrunk)
    result = run_in_process("resnet50-eager-1rank", monkeypatch)
    assert result["correct"] is False
    assert not result["checks"]["grad_norm_gap"]["ok"]


def test_a_chips_rows_left_out_of_a_step_over_four_are_not_correct(
        monkeypatch):
    """The step over four devices as it is, fed the first chip's rows
    in the last chip's place: a part of the batch never reaches the
    gradients' mean, and the first gradient's norm gives it away."""
    import jax
    import jax.numpy as jnp
    real = harness.Program.step

    def short(self, state, batch, stop=0.0):
        n = self.sz["per_chip_batch"]
        batch = tuple(jax.device_put(
            jnp.concatenate([a[:-n], a[:n]]), a.sharding) for a in batch)
        return real(self, state, batch, stop)

    sound = run_in_process("lm-injit-4chip", monkeypatch)
    assert sound["correct"] and sound["device"]["count"] >= 4
    result = run_in_process("lm-injit-4chip", monkeypatch, step=short)
    assert result["correct"] is False
    assert not result["checks"]["grad_norm_gap"]["ok"]
    assert result["checks"]["replay_loss_gap"]["ok"]
