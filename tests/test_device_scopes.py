"""Device time by scope comes from the program: the vocabulary is data
and every scope the package opens is in it, ``spmd.device_scopes``
reads an executable's text as the rules say, and the jitted steps note
what they compile only under an armed trace (docs/tracing.md, "On the
device")."""

import ast
import os

import pytest

from horovod_tpu import spmd
from horovod_tpu.common import trace as htrace
from horovod_tpu.spmd import scopes

pytestmark = pytest.mark.time_limit(60)

PKG = os.path.dirname(os.path.abspath(spmd.__file__ + "/.."))
# the flax names that stand for a part no ``named_scope`` names: where
# a model gives a module that name
FLAX_NAMES = {"attn", "mlp", "embed", "moe"}


def literals():
    """``(opened, named)``: every ``jax.named_scope("...")`` literal
    under the package, and every ``name="..."`` keyword of a call."""
    opened, named = {}, set()
    for base, _, files in os.walk(PKG):
        for f in (f for f in files if f.endswith(".py")):
            path = os.path.join(base, f)
            with open(path) as fh:
                tree = ast.parse(fh.read())
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                named |= {k.value.value for k in node.keywords
                          if k.arg == "name"
                          and isinstance(k.value, ast.Constant)}
                if getattr(node.func, "attr", "") != "named_scope":
                    continue
                arg = node.args[0]
                if isinstance(arg, ast.Constant):
                    opened.setdefault(arg.value, path)
                else:       # phi4flash picks one of three by the layer
                    src = ast.unparse(arg)
                    assert src == "scope", (path, src)
    return opened, named


def test_every_scope_opened_is_in_the_vocabulary_and_every_key_is_used():
    opened, named = literals()
    # the one computed scope: phi4flash's differential attention
    opened |= dict.fromkeys(
        ("diff_attn", "diff_attn.window", "diff_attn.cross"), "phi4flash")
    assert len(opened) > 20
    assert set(opened) - set(htrace.DEVICE_SCOPES) == set()
    unused = set(htrace.DEVICE_SCOPES) - set(opened)
    assert unused <= FLAX_NAMES and unused <= named, unused
    assert FLAX_NAMES <= set(htrace.DEVICE_SCOPES)
    for what in htrace.DEVICE_SCOPES.values():
        assert what and "\n" not in what


def test_the_three_differential_attention_scopes_are_the_models():
    with open(os.path.join(PKG, "models", "phi4flash.py")) as f:
        text = f.read()
    for name in ("diff_attn.window", "diff_attn", "diff_attn.cross"):
        assert f'"{name}"' in text


# -- a hand-written executable ------------------------------------------

def meta(path):
    return f'metadata={{op_name="{path}" stack_frame_id=7}}'


STEP = "jit(step)/shard_map"
HLO = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  ROOT %mul.1 = f32[8]{{0}} multiply(%p, %p), {meta(STEP + "/loss/jvp(LM)/block_0/mlp/mul")}
}}

%fused_computation.2 (p: f32[8]) -> f32[8] {{
  %p.2 = f32[8]{{0}} parameter(0)
  ROOT %add.2 = f32[8]{{0}} add(%p.2, %p.2), {meta("loss/jvp(lm_head_loss)/while/body/closed_call/add")}
}}

%add (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b)
}}

%body (carry: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %carry = (s32[], f32[8]{{0}}) parameter(0)
  %gte.1 = f32[8]{{0}} get-tuple-element(%carry), index=1
  %fusion.20 = f32[8]{{0}} fusion(%gte.1), kind=kLoop, calls=%fused_computation.2, {meta(STEP + "/loss/jvp(lm_head_loss)/while/body/closed_call/dot_general")}
  %fusion.21 = f32[8]{{0}} fusion(%fusion.20), kind=kLoop, calls=%fused_computation.2
  ROOT %tuple.1 = (s32[], f32[8]{{0}}) tuple(%gte.0, %fusion.21)
}}

%cond (carry.1: (s32[], f32[8])) -> pred[] {{
  %carry.1 = (s32[], f32[8]{{0}}) parameter(0)
  ROOT %lt = pred[] compare(%gte.2, %c), direction=LT, {meta(STEP + "/loss/jvp(lm_head_loss)/while/cond/lt")}
}}

%branch_0 (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0)
  %fusion.30 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.1, {meta(STEP + "/loss/jvp(LM)/layer_0/moe/cond/branch_0_fun/moe.dispatch/gather")}
  %ragged-dot-none.3 = f32[8]{{0}} custom-call(%fusion.30), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %fusion.33 = f32[8]{{0}} fusion(%ragged-dot-none.3), kind=kLoop, calls=%fused_computation.1, {meta(STEP + "/loss/jvp(LM)/layer_0/moe/cond/branch_0_fun/moe.experts/mul")}
  %ragged-dot-none.4 = f32[8]{{0}} custom-call(%fusion.33), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  %copy.7 = f32[8]{{0}} copy(%fusion.33)
  ROOT %tuple.3 = (f32[8]{{0}}, f32[8]{{0}}, f32[8]{{0}}) tuple(%ragged-dot-none.4, %fusion.31, %copy.7)
  %fusion.31 = f32[8]{{0}} fusion(%fusion.33), kind=kLoop, calls=%fused_computation.1, {meta(STEP + "/loss/jvp(LM)/layer_0/moe/cond/branch_0_fun/moe.combine/mul")}
}}

%branch_1 (y: f32[8]) -> f32[8] {{
  %y = f32[8]{{0}} parameter(0)
  ROOT %fusion.32 = f32[8]{{0}} fusion(%y), kind=kLoop, calls=%fused_computation.1, {meta(STEP + "/loss/jvp(LM)/layer_0/moe/cond/branch_1_fun/while/body/moe.combine/mul")}
}}

ENTRY %main.9 (a: f32[8], i: s32[]) -> f32[8] {{
  %a.1 = f32[8]{{0}} parameter(0)
  %fusion.1 = f32[8]{{0}} fusion(%a.1), kind=kLoop, calls=%fused_computation.1
  %copy.4 = f32[8]{{0}} copy(%a.1)
  %bitcast.9 = f32[8]{{0}} bitcast(%copy.4)
  %sort.2 = f32[8]{{0}} sort(%a.1), dimensions={{0}}, to_apply=%add
  %flash_fwd.12 = f32[8]{{0}} custom-call(%fusion.1, %bitcast.9), custom_call_target="tpu_custom_call", {meta(STEP + "/loss/jvp(LM)/block_0/attn/jit(_flash_bhsd)/flash_fwd/pallas_call")}
  %while.4 = (s32[], f32[8]{{0}}) while(%tuple.0), condition=%cond, body=%body, {meta(STEP + "/loss/jvp(lm_head_loss)/while")}
  %conditional.8 = f32[8]{{0}} conditional(%i, %a.1, %a.1), branch_computations={{%branch_0, %branch_1}}
  %fusion.40 = f32[8]{{0}} fusion(%sort.2), kind=kLoop, calls=%fused_computation.1, {meta(STEP + "/loss/transpose(jvp(lm_head_loss))/mul")}
  %fusion.41 = f32[8]{{0}} fusion(%a.1), kind=kLoop, calls=%fused_computation.1, {meta(STEP + "/loss/mtp/jvp(LM)/mtp/block/attn/mla/jvp(lm_head_loss)/while/body/add")}
  %fusion.42 = f32[8]{{0}} fusion(%a.1), kind=kLoop, calls=%fused_computation.1, {meta(STEP + "/loss/jvp(LM)/block_0/attn/jit(mla)/mul")}
  %psum.797 = f32[8]{{0}} all-reduce(%fusion.40), channel_id=1, to_apply=%add
  %all-reduce.5 = f32[8]{{0}} all-reduce(%fusion.40), channel_id=2, to_apply=%add, {meta(STEP + "/optimizer/exchange/psum")}
  %all-reduce.6 = f32[8]{{0}} all-reduce(%fusion.40), channel_id=3, to_apply=%add, {meta(STEP + "/loss/jvp(ResNet)/bn/psum")}
  %async-collective-start.2 = f32[8]{{0}} fusion(%fusion.40), kind=kCustom, calls=%fused_computation.1
  %async-collective-done.2 = f32[8]{{0}} fusion(%async-collective-start.2), kind=kCustom, calls=%fused_computation.1, {meta(STEP + "/optimizer/exchange/psum")}
  %copy.3 = f32[8]{{0}} copy(%a.1)
  %fusion.50 = f32[8]{{0}} fusion(%a.1), kind=kLoop, calls=%fused_computation.1, {meta(STEP + "/convert.12")}
  %fusion.51 = f32[8]{{0}} fusion(%sort.2), kind=kLoop, calls=%fused_computation.1, {meta(STEP + "/optimizer/add")}
  ROOT %copy.6 = f32[8]{{0}} copy(%fusion.51)
}}
"""

EXPECTED = {
    "fusion.1": "mlp",              # no metadata: its fused root's
    "flash_fwd.12": "attn",
    "while.4": "lm_head_loss",
    "fusion.20": "lm_head_loss",    # a fusion in the while's body
    "fusion.21": "lm_head_loss",    # the same without metadata of its own
    "lt": "lm_head_loss",           # the while's condition
    "conditional.8": "moe",         # no path: what its branches share
    "fusion.30": "moe.dispatch",    # a conditional's branch
    "ragged-dot-none.3": "moe",     # a name for a path: its computation's
    "ragged-dot-none.4": "moe",
    "fusion.31": "moe.combine",
    "fusion.32": "moe.combine",
    "fusion.40": "lm_head_loss",    # transpose(jvp(lm_head_loss))
    "fusion.41": "lm_head_loss",    # nested: the innermost
    "fusion.42": "attn",            # jit(mla) names a function, no scope
    "psum.797": "exchange",         # a scopeless all-reduce
    "all-reduce.5": "exchange",
    "all-reduce.6": "loss",         # a collective that carries a scope
    "async-collective-start.2": "exchange",     # not its root's `mlp`
    "async-collective-done.2": "exchange",
    "copy.3": "",                   # no metadata, no neighbour, no shared scope
    "copy.4": "attn",               # the compiler's copy is its user's work
    "bitcast.9": "attn",
    "sort.2": "",                   # its users share no scope
    "copy.6": "optimizer",          # no user: its operand's
    "copy.7": "moe.experts",
    "fusion.50": "",                # a path with no scope on it
    "fusion.51": "optimizer",
}


def test_device_scopes_reads_a_hand_written_executable():
    table = spmd.device_scopes(HLO)
    assert {k: table[k] for k in EXPECTED} == EXPECTED
    # fused computations' and reducers' instructions are no events
    for inner in ("mul.1", "add.2", "sum", "p"):
        assert inner not in table
    assert table.backward == {"fusion.40"}
    # an event's name as a profile shows it
    for shown, scope in (
            ("flash_fwd.12[tpu_custom_call]", "attn"),
            ("psum.797[all-reduce]", "exchange"),
            ("%fusion.31 = f32[8]{0} fusion(%ragged-dot-none.3), kind=kLoop",
             "moe.combine"),
            ("fusion.50", ""), ("fusion.999", None)):
        assert spmd.scope_of(table, shown) == scope
    # another vocabulary, another table
    assert spmd.device_scopes(HLO, {"loss": ""})["fusion.30"] == "loss"


# -- the step notes its executable only under an armed trace ---------------

@pytest.fixture
def disarmed():
    htrace._reset_spans_for_tests()
    scopes._forget_noted_for_tests()
    yield
    htrace._reset_spans_for_tests()
    scopes._forget_noted_for_tests()


def tiny_step(mesh):
    import jax
    from horovod_tpu.models import train_steps

    def step(x):
        with jax.named_scope("optimizer"):
            return x * 2.0 + 1.0
    return train_steps._jit_step(step, mesh, donate_argnums=())


def test_the_step_is_the_jit_object_and_notes_nothing_when_off(disarmed):
    import jax
    import jax.numpy as jnp
    mesh = spmd.create_mesh({"data": 1}, devices=jax.devices()[:1])
    step = tiny_step(mesh)
    assert type(step) is type(jax.jit(lambda x: x))
    compiled = step.lower(jnp.ones(8)).compile()
    assert isinstance(compiled, jax.stages.Compiled)
    assert spmd.noted_device_scopes() is None
    # and so is every step the module builds
    from horovod_tpu.models import train_steps
    from horovod_tpu.models.transformer import (
        TransformerConfig, TransformerLM)
    model = TransformerLM(TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
        max_seq_len=16))
    built = train_steps.lm_train_step(
        model, train_steps.distributed_sgd(), mesh)
    assert type(built) is type(step)
    htrace.arm_spans(True)
    assert type(train_steps.lm_train_step(
        model, train_steps.distributed_sgd(), mesh)) is train_steps._NotedStep


def test_armed_the_step_notes_what_it_compiles_and_reads_it_when_asked(
        disarmed, monkeypatch):
    import jax
    import jax.numpy as jnp
    htrace.arm_spans(True)
    mesh = spmd.create_mesh({"data": 1}, devices=jax.devices()[:1])
    step = tiny_step(mesh)
    assert type(step) is not type(jax.jit(lambda x: x))
    assert float(step(jnp.ones(8))[0]) == 3.0       # called as the jit
    read = []
    real = scopes.device_scopes
    monkeypatch.setattr(scopes, "device_scopes",
                        lambda c: read.append(c) or real(c))
    compiled = step.lower(jnp.ones(8)).compile()
    assert type(compiled) is jax.stages.Compiled
    assert read == []                   # nothing read at compile
    table = spmd.noted_device_scopes()
    assert read == [compiled]
    assert table == real(compiled) and "optimizer" in table.values()
    assert spmd.noted_device_scopes() is table and len(read) == 1
    # the note is weak: it keeps no executable alive
    other = step.lower(jnp.ones(4)).compile()
    del other
    assert spmd.noted_device_scopes() is None
